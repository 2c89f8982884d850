#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload qwen3-4b.pd-cold --seed 7 --seconds 30 --trace 0

One process: draw the cell's bf16 weights on the chip from ``--seed``,
build a ``ClusterRuntime`` with the cell's traffic mix, warm up every
shape the traffic uses, then offer the mix's open-loop arrivals for
``--seconds`` wall seconds, drain the requests that fell due in the window,
and check a seeded sample of them against the plain float32 reference of
the architecture that the configuration file names (``"harness"``: a
module under ``bench/archs/``, which also gives the counts and the size
check).
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, and with ``--trace 1``
``breakdown``); its last key, ``checked``, and the last lines of standard
error give each compared number beside its limit.

``--trace 1`` reads the per-layer metrics (``bench/metrics/<name>.py``)
from a profiler trace of the window; ``--trace 0`` reports the end-to-end
ones.  Without a TPU the run fails, unless ``--rehearse`` asks for the
cell's reduced configuration on whatever JAX finds (a rehearsal prints no
device metric).  ``--control fp8`` judges the comparison's
lower-precision control in the program's place: the tokens the reference
in fp8 ranks first, at every position of the same sample, go through the
same comparison, and a sound harness prints ``"correct": false``.
``--seeds a,b,...`` / ``--rates r,s,...`` repeat the run for each seed and
arrival rate in this one process; they serve setting the limit and finding
the knee, never the measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import statistics
import sys
import tempfile
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402  (bench/traffic.py)
from archs import CONTRACT  # noqa: E402  (bench/archs/__init__.py)

ARCHS = BENCH / "archs"

WARM_PROMPT_SEED = 424242
DRAIN_LIMIT_S = 150.0


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
class Cell:
    def __init__(self, workload: str):
        spec_path = ROOT / "BENCHMARK.json"
        if not spec_path.exists():
            fail("no BENCHMARK.json at the checkout root")
        self.spec = json.loads(spec_path.read_text())
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            fail(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = json.loads((ROOT / self.config_entry["file"]).read_text())
        self.harness = load_harness(self.config, self.config_entry["file"])
        self.mix = json.loads(
            (BENCH / "traffic" / f"{self.workload['traffic']}.json").read_text())
        if self.mix["mode"] != "pd":
            fail(f"mix {self.workload['traffic']!r}: this harness serves "
                 f"PD mixes only (mode {self.mix['mode']!r})")
        self.profiles_path = BENCH / "profiles" / f"{self.config['name']}.jsonl"
        self.chips = int(self.workload["chips"])

    @property
    def model(self) -> Dict:
        """Sizes and architecture flags, as the reference and the counts
        read them."""
        return {**self.config["used"], **self.config["architecture"]}

    def per_layer(self) -> List[Dict]:
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def load_harness(config: Dict, config_file: str, where: Path = ARCHS):
    """The architecture module ``<where>/<harness>.py`` that the
    configuration file names: its reference, counts and size check (the
    contract is in ``bench/archs/__init__.py``)."""
    name = config.get("harness")
    if not name:
        fail(f"{config_file} names no \"harness\" (a module under "
             f"{ARCHS.relative_to(ROOT)}/)")
    path = where / f"{name}.py"
    if not path.is_file():
        fail(f"{config_file}: harness {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in CONTRACT if not hasattr(mod, n)]
    if missing:
        fail(f"{path} (the harness of {config_file}) lacks {missing}")
    return mod


# ---------------------------------------------------------------------------
# JAX and the program
# ---------------------------------------------------------------------------
def start_jax(rehearse: bool, chips: int):
    os.environ.setdefault("REPRO_CACHE_DIR", str(ROOT / ".repro_cache"))
    if not (ROOT / "src" / "repro").is_dir():
        fail("no src/repro in this checkout: the program under test is "
             "missing")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    # One fixed directory inside the checkout, so that only a checkout's
    # first run compiles; every program is kept, however fast it compiled.
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        fail(f"JAX found no TPU (platform {devices[0].platform!r}); the "
             f"benchmark measures only on the chip")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return jax, devices


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while ``on``."""

    def __init__(self, jax):
        self.on = False
        self.events: Dict[str, int] = {}

        def listen(event: str, duration: float, **kwargs):
            if self.on and ("backend_compile" in event
                            or "cache_retrieval" in event):
                self.events[event] = self.events.get(event, 0) + 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def load_controller(cell: Cell):
    from repro.controller import ServiceAwareController
    from repro.core.profiles import load_profiles

    profiles = load_profiles(str(cell.profiles_path))
    names = [p.strategy.short_name() for p in profiles]
    if len(set(names)) != len(names):
        fail(f"profile names must be unique (the check maps a served "
             f"request's profile name to its strategy): {names}")
    return ServiceAwareController(
        {w: profiles for w in cell.mix["prompt_families"]})


def strategies_by_name(cell: Cell) -> Dict[str, Dict]:
    """Served profile name -> the strategy's fields as plain data (what
    the reference reads; it imports nothing of the program)."""
    from dataclasses import asdict

    from repro.core.profiles import load_profiles

    return {p.strategy.short_name(): asdict(p.strategy)
            for p in load_profiles(str(cell.profiles_path))}


def build_runtime(cell: Cell, cfg, params, controller, horizon_s: float):
    from repro.serving.cluster import ClusterRuntime
    from repro.serving.network import BandwidthTrace
    from repro.serving.scheduler import SchedulerConfig
    from repro.serving.workers import RuntimeConfig

    mix = cell.mix
    rc = RuntimeConfig(
        seq=int(mix["prompt_tokens"]), decode_tokens=int(mix["output_tokens"]),
        mode="pd", prefill_tok_s=None, decode_tok_s=None,
        pd_inject_restored=bool(mix["pd_inject_restored"]), paged=False)
    sc = SchedulerConfig(max_slots=int(mix["slots"]),
                         max_prefills_per_step=int(
                             mix.get("max_prefills_per_step", 1)),
                         max_queue=int(mix["max_queue"]))
    link = BandwidthTrace.steps(
        [tuple(s) for s in traffic.link_segments(mix, horizon_s)])
    rt = ClusterRuntime(controller=controller, config=rc, scheduler=sc,
                        trace=link)
    rt.model_cfg, rt.params = cfg, params
    return rt


def warm_up(cell: Cell, rt) -> None:
    """Compile every program the window will run: the whole start-of-life
    path and the decode step (two requests), and the restored-KV injection
    into every arena slot (its slot index is a static argument), with the
    stand-in hand-off of the configuration's architecture module."""
    import jax

    from repro.core.kvcache import KVCache

    mix = cell.mix
    fam = mix["prompt_families"][0]

    def serve_pair(base: int) -> None:
        for s in (base, base + 1):
            rt.submit(fam, q_min=float(mix["q_min"]), prompt_seed=s)
            rt.run()

    serve_pair(WARM_PROMPT_SEED)
    dw = rt.decode_workers[0]
    kv = KVCache(*cell.harness.warm_handoff(rt.model_cfg,
                                            int(mix["prompt_tokens"])))
    for idx in range(dw.n_slots):
        dw.inject_restored(kv, idx)
    # Once written from the host, the arena's arrays are committed to the
    # device, and jit keys its programs on that: serve again over them.
    serve_pair(WARM_PROMPT_SEED + 2)
    jax.block_until_ready(dw._arena)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
class Result:
    pass


def run_once(cell: Cell, args, seed: int, rate: float, jax, devices,
             counter, t_setup0: float) -> Result:
    from weights import draw_params
    from repro.configs import get_config

    import spans as spans_mod

    res = Result()
    mix = cell.mix
    name = cell.config["registry_name"] + ("-reduced" if args.rehearse else "")
    cfg = get_config(name)
    check_sizes(cell, cfg, args.rehearse)
    t = time.perf_counter()
    params = draw_params(cfg, seed)
    jax.block_until_ready(params)
    say(f"weights drawn in {time.perf_counter() - t:.2f} s")
    strategies = strategies_by_name(cell)
    horizon = max(8.0 * args.seconds, 600.0)

    t = time.perf_counter()
    rt = build_runtime(cell, cfg, params, load_controller(cell), horizon)
    warm_up(cell, rt)
    del rt
    gc.collect()
    say(f"warm-up in {time.perf_counter() - t:.2f} s")

    arrivals = traffic.schedule(mix, seed, horizon, rate=rate)
    rt = build_runtime(cell, cfg, params, load_controller(cell), horizon)
    recorder = spans_mod.Recorder().install()
    prompts: Dict[int, np.ndarray] = {}
    due_of: Dict[int, float] = {}
    shed = 0
    live: List[int] = []          # requests in the arena after each step
    q_min = float(mix["q_min"])

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    # ---- the measured window --------------------------------------------
    res.setup_s = time.perf_counter() - t_setup0
    recorder.on = counter.on = True
    t0 = time.perf_counter()
    i = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < args.seconds:
            while i < len(arrivals) and arrivals[i].due <= rt.clock:
                a = arrivals[i]
                rid = rt.submit(a.family, q_min=q_min,
                                prompt_seed=a.prompt_seed)
                if rid is None:
                    shed += 1
                else:
                    prompts[rid] = np.array(rt._prompts[rid], np.int32)
                    due_of[rid] = a.due
                i += 1
            if rt.scheduler.idle:
                if i >= len(arrivals):
                    break
                rt.clock = max(rt.clock, arrivals[i].due)
                continue
            rt.step()
            live.append(len(rt._slots))
    t1 = time.perf_counter()
    res.slots_live_peak = max(live, default=0)
    res.slots_live_mean = statistics.fmean(live) if live else 0.0
    res.queue_at_close = rt.scheduler.queue_depth
    res.clock_at_close = rt.clock
    counter.on = False
    if args.trace:
        jax.profiler.stop_trace()
    res.window_s = t1 - t0
    res.compiles = dict(counter.events)
    res.lateness = [r.arrival - due_of[r.rid] for r in rt.completed]
    done_in_window = sum(len(r.tokens) for r in rt.completed)
    in_flight = sum(len(s.toks) for s in rt._slots.values())
    res.tok_per_s = (done_in_window + in_flight) / res.window_s

    # ---- drain the window's requests, no new arrivals -------------------
    t_drain = time.perf_counter()
    while (not rt.scheduler.idle
           and time.perf_counter() - t_drain < DRAIN_LIMIT_S):
        rt.step()
    recorder.on = False
    res.drain_s = time.perf_counter() - t_drain
    done = {r.rid: r for r in rt.completed if r.rid in due_of}
    res.attempted = i
    res.failed = shed + (len(due_of) - len(done))
    rows = []
    for rid, r in sorted(done.items()):
        late = r.arrival - due_of[rid]
        rows.append({
            "rid": rid, "ttft": r.ttft + late, "jct": r.jct + late,
            "tpot": (r.jct - r.ttft) / max(len(r.tokens) - 1, 1),
            "breakdown": dict(r.breakdown), "kv_bytes": int(r.kv_bytes), "wire_bytes": int(r.wire_bytes),
            "profile": r.profile, "tokens": np.asarray(r.tokens, np.int32)})
    res.rows = rows
    res.profiles_used = sorted({r["profile"] for r in rows})
    res.memory_peak = None
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        res.memory_peak = int(stats["peak_bytes_in_use"])
    recorder.uninstall()
    res.recorder = recorder
    res.trace_dir = trace_dir

    # ---- free the program's state before the reference runs -------------
    res.sample = pick_sample(cell, rows, seed, strategies, prompts)
    del rt, params, done
    gc.collect()
    res.live_bytes = sum(a.nbytes for a in jax.live_arrays())
    return res


def check_sizes(cell: Cell, cfg, rehearse: bool) -> None:
    """The program's registered sizes must be the configuration file's."""
    used = cell.config["rehearsal"] if rehearse else cell.config["used"]
    bad = cell.harness.check(cfg, used, cell.config["architecture"])
    if bad:
        fail(f"the program's {cfg.name} differs from "
             f"{cell.config_entry['file']}: {bad}")


def pick_sample(cell: Cell, rows, seed: int, strategies, prompts):
    """The requests the reference checks, drawn from the seed."""
    from reference import Served

    k = int(cell.mix["sample_requests"])
    rng = np.random.default_rng([seed, 3])
    picked = [rows[j] for j in rng.permutation(len(rows))[:k]]
    handed = bool(cell.mix["pd_inject_restored"])
    out = []
    for r in sorted(picked, key=lambda r: r["rid"]):
        st = strategies.get(r["profile"]) if handed else None
        if handed and st is None:
            fail(f"served profile {r['profile']!r} is not in "
                 f"{cell.profiles_path.name}")
        out.append(Served(prompt=prompts[r["rid"]], tokens=r["tokens"],
                          strategy=st))
    return out


def compare(cell: Cell, res: Result, seed: int, rehearse: bool,
            control: bool):
    """Widest gap of the served tokens, and with ``control`` that of the
    fp8 control's tokens (None where nothing finished)."""
    model = dict(cell.config["rehearsal" if rehearse else "used"])
    ref = cell.harness.Reference(model, cell.config["architecture"], seed)
    if not res.sample:
        return None, None
    gaps, ctrl = ref.gaps(res.sample, control=control)
    return widest(gaps), (widest(ctrl) if control else None)


def widest(gaps) -> float:
    return max(float(np.max(g)) for g in gaps)


# ---------------------------------------------------------------------------
def percentile(values, p) -> float:
    return float(np.percentile(np.asarray(values, float), p))


def end_to_end(res: Result) -> Dict[str, float]:
    ttft = [r["ttft"] for r in res.rows]
    tpot = [r["tpot"] for r in res.rows]
    out = {"setup_s": res.setup_s, "tok_per_s": res.tok_per_s}
    if ttft:
        out.update(ttft_p95_s=percentile(ttft, 95),
                   ttft_p50_s=percentile(ttft, 50),
                   tpot_p95_s=percentile(tpot, 95))
    return out


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric reads."""

    def __init__(self, cell: Cell, res: Result, trace, peak):
        self.cell, self.mix, self.model = cell, cell.mix, cell.model
        self.rows, self.spans, self.trace = res.rows, res.recorder, trace
        self.peak, self.counts = peak, cell.harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the reduced configuration on any JAX backend")
    ap.add_argument("--control", choices=("fp8",), default=None)
    ap.add_argument("--rates", default="",
                    help="arrivals per cluster second, comma-separated, one "
                         "run each (the knee sweep); default: the mix's")
    ap.add_argument("--seeds", default="",
                    help="comma-separated seeds, one run each, one process")
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    jax, devices = start_jax(args.rehearse, cell.chips)
    counter = CompileCounter(jax)
    kind = devices[0].device_kind
    peak = None
    if not args.rehearse:
        import counts
        try:
            peak = counts.peak_for(kind)
        except KeyError as e:
            fail(str(e))
    limits = cell.config["limits"]["rehearsal" if args.rehearse else "chip"]
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [0.0])

    line = None
    t_setup0 = T_START
    for seed, rate in [(s, r) for s in seeds for r in rates]:
        rate = rate or traffic.rate_rps(cell.mix)
        res = run_once(cell, args, seed, rate, jax, devices, counter,
                       t_setup0)
        t_ref = time.perf_counter()
        prog_gap, ctrl_gap = compare(cell, res, seed, args.rehearse,
                                     bool(args.control))
        ref_s = time.perf_counter() - t_ref
        # Under --control the control's tokens stand in the program's
        # place and go through the same comparison.
        max_gap = ctrl_gap if args.control else prog_gap
        checked = {"max_logit_gap": {"value": max_gap,
                                     "limit": limits["max_logit_gap"]}}
        correct = (max_gap is not None
                   and max_gap <= limits["max_logit_gap"]
                   and res.failed == 0)
        lat = res.lateness
        say(f"seed {seed}: window {res.window_s:.3f} s, {res.attempted} "
            f"requests due in it ({len(res.rows)} finished, {res.failed} "
            f"failed), generator lateness on the cluster clock mean "
            f"{statistics.fmean(lat) if lat else 0:.6f} s max "
            f"{max(lat) if lat else 0:.6f} s; drain {res.drain_s:.1f} s; "
            f"reference {ref_s:.1f} s over {len(res.sample)} requests; "
            f"compiles in the window {res.compiles or 0}; profiles served "
            f"{res.profiles_used}")
        parts = {}
        for r in res.rows:
            for k2, v2 in r["breakdown"].items():
                parts[k2] = parts.get(k2, 0.0) + v2 / len(res.rows)
        say(f"seed {seed} rate {rate}: "
            f"cluster clock {res.clock_at_close:.2f} s at the close, "
            f"{res.queue_at_close} waiting; mean breakdown "
            + ", ".join(f"{k2} {v2:.4f}" for k2, v2 in sorted(parts.items()))
            + f"; ttft {sorted(round(r['ttft'], 3) for r in res.rows)}"
            + f"; live device bytes before the reference {res.live_bytes}")
        say(f"seed {seed}: arena slots {cell.mix['slots']}, live after "
            f"each step peak {res.slots_live_peak} mean "
            f"{res.slots_live_mean:.3f}")
        if args.control:
            say(f"seed {seed}: control {args.control} max gap {ctrl_gap!r} "
                f"judged in the program's place; program max gap "
                f"{prog_gap!r}")
        metrics = {m["name"]: {"value": end_to_end(res)[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end()
                   if m["name"] in end_to_end(res)}
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": cell.chips, "memory_peak_bytes": res.memory_peak}
        line = {"correct": bool(correct), "attempted": res.attempted,
                "failed": res.failed}
        if args.trace:
            from devtrace import Trace

            tr = Trace.from_dir(res.trace_dir) if res.trace_dir else None
            ctx = Context(cell, res, tr, peak)
            metrics = {}
            for m in cell.per_layer():
                if m["source"] == "device_trace" and (tr is None
                                                      or not tr.has_device):
                    continue
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if tr is not None:
                device.update(busy_s=tr.busy_s, window_s=tr.window_s)
                line["breakdown"] = {"device_ops": tr.top_ops(10),
                                     "idle_gaps": tr.idle_gaps(10)}
            import shutil
            shutil.rmtree(res.trace_dir, ignore_errors=True)
        if args.rehearse:
            line["rehearse"] = True
        line["metrics"] = metrics
        line["device"] = device
        line["window"] = {"requests": res.attempted,
                          "waiting_at_close": res.queue_at_close,
                          "cluster_s": res.clock_at_close,
                          "lateness_max_s": max(lat) if lat else 0.0,
                          "slots_live_peak": res.slots_live_peak,
                          "slots_live_mean": res.slots_live_mean}
        if args.control:
            line["control"] = {"name": args.control,
                               "program_max_logit_gap": prog_gap}
        line["checked"] = checked
        if len(seeds) > 1 or len(rates) > 1:
            print(json.dumps({"seed": seed, "rate": rate, **line}),
                  flush=True)
        t_setup0 = time.perf_counter()
        del res
        gc.collect()
    for k, v in line["checked"].items():
        say(f"checked {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
