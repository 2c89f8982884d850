"""repro.analysis: per-rule positive/negative fixtures + suppression
grammar + the no-dead-rules meta-test (DESIGN.md §13).

Fixture convention: each entry is ``(fires, {relpath: source})`` — a tiny
project written to tmp_path.  ``fires=True`` fixtures exhibit the bug
class and MUST produce at least one finding of their rule;
``fires=False`` fixtures are the idiomatic clean shape and must produce
none.  Every registered rule needs at least one of each (no dead rules).
"""
import json
from pathlib import Path

import pytest

from repro.analysis import ALL_RULES
from repro.analysis.cli import main, run_paths

REPO = Path(__file__).resolve().parents[1]

# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------
SYNC_BAD = """\
import jax
import jax.numpy as jnp
import numpy as np

def step(state):
    logits = jnp.ones((4, 8))
    tok = np.asarray(jnp.argmax(logits, axis=-1))
    jax.block_until_ready(logits)
    n = int(logits.sum())
    return tok, n
"""

SYNC_CLEAN = """\
import numpy as np

def step(batch):
    toks = np.asarray(batch["tokens"])   # host data: no device taint
    return toks.sum()
"""

SYNC_LAUNDERED = """\
import jax.numpy as jnp
import numpy as np

def helper(x):
    return [int(v) for v in x]

def step(state):
    logits = jnp.ones((4, 8))
    # lint: sync-ok(fixture - the sanctioned once-per-iteration pull)
    host = np.asarray(logits)
    n = int(host.sum())        # host value: laundered, no finding
    hv = helper(logits)        # project def: result is host
    m = float(hv[0])
    return n, m
"""

# ---------------------------------------------------------------------------
# clock-accounting
# ---------------------------------------------------------------------------
CLOCK_DEAD_T = """\
def bill(req, now):
    t_comm = 0.25              # computed, never billed anywhere
    req.breakdown["queue"] = now - req.arrival
    return req
"""

CLOCK_DOUBLE = """\
def bill(req, t_comm):
    req.breakdown["comm"] = t_comm
    req.breakdown["comm"] = 2 * t_comm   # first component dropped
    return req
"""

CLOCK_BACKWARDS = """\
class Wire:
    def send(self, ready, t_comm):
        self.free_at = ready + t_comm    # can move the clock backwards
        return t_comm
"""

CLOCK_CLEAN = """\
class Wire:
    def __init__(self):
        self.free_at = 0.0               # __init__ is exempt

    def send(self, ready, t_comm):
        start = max(ready, self.free_at)
        self.free_at = start + t_comm    # derived from max(): monotone
        return start

def bill(req, now, t_comm):
    if req.hit:
        req.breakdown["comm"] = t_comm   # branches are separate paths
    else:
        req.breakdown["comm"] = 2 * t_comm
    req.breakdown["queue"] = now - req.arrival
    req.breakdown["queue"] += 0.5        # += accumulates, never flags
    return t_comm
"""

# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------
UNITS_MIX = """\
def route_cost(wire_bytes, free_at, t_slo, bandwidth):
    score = wire_bytes + free_at         # bytes + seconds
    if wire_bytes > t_slo:               # bytes vs seconds
        score += 1.0
    t_comm = wire_bytes                  # seconds name, bytes value
    return score + t_comm
"""

UNITS_CLEAN = """\
def route_cost(wire_bytes, free_at, now, bandwidth, ctx_tokens,
               prefill_tok_s):
    t_comm = wire_bytes / bandwidth          # bytes / (bytes/s) -> s
    t_prefill = ctx_tokens / prefill_tok_s   # tokens / (tokens/s) -> s
    wait = max(free_at - now, 0.0)
    payload = bandwidth * t_comm             # (bytes/s) * s -> bytes
    return t_comm + t_prefill + wait, payload + wire_bytes
"""

# ---------------------------------------------------------------------------
# kernel-contract
# ---------------------------------------------------------------------------
KC_INIT_BAD = """\
from pkg.kernels.ops import foo_op

__all__ = ["foo_op"]
"""
KC_REF_BAD = """\
def bar_ref(x):                 # orphan: no export, no oracle uses it
    return x
"""
KC_OPS_BAD = """\
def foo_op(x):                  # no interpret fallback
    return x
"""

KC_INIT_OK = """\
from pkg.kernels.ops import foo_op

__all__ = ["foo_op"]
"""
KC_REF_OK = """\
def _scale_ref(x):
    return x * 2

def foo_ref(x):
    return _scale_ref(x)
"""
KC_OPS_OK = """\
def foo_op(x, interpret=None):
    return x
"""
KC_TEST_OK = """\
def test_foo_parity():
    assert foo_op is not None and foo_ref is not None
"""

# ---------------------------------------------------------------------------
# ownership
# ---------------------------------------------------------------------------
OWN_BAD = """\
class PoolRuntime:
    def __init__(self, spec):
        self.scheduler = ContinuousScheduler()
        self._shared_remote = KVTier(spec)
        self._shared_remote.shared = True

    def rebind(self):
        self.scheduler = ContinuousScheduler()   # other holders keep old

    def poke(self):
        self.scheduler._queue.append(1)          # bypasses the owner API

    def promote(self, hit):
        return hit.tier.store._entries.pop(hit.key)   # MOVE, unguarded

    def choose_worker(self, routes):
        for r in set(routes):                    # hash-order decision
            return r
"""

OWN_CLEAN = """\
from dataclasses import replace


class PoolRuntime:
    def __init__(self, spec):
        self.scheduler = ContinuousScheduler()
        self._shared_remote = KVTier(spec)
        self._shared_remote.shared = True        # construction site: owner
        self.tiers = [KVTier(spec), self._shared_remote]

    def promote(self, hit):
        if hit.tier.shared:
            return replace(hit.entry)            # COPY out of the pool
        return hit.tier.store._entries.pop(hit.key)   # proven local

    def refresh(self, key):
        for t in self.tiers:
            if t.shared:
                continue                         # never clobber the pool
            t.store.discard(key)

    def choose_worker(self, routes):
        return min(routes, key=lambda r: r.index)    # stable field
"""

# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
DET_BAD = """\
import random
import time

import numpy as np


def _run_events(cfg):
    t0 = time.perf_counter()                 # wall clock in virtual time
    rng = np.random.default_rng()            # entropy-seeded
    jit = np.random.normal()                 # legacy global-state API
    x = random.random()                      # stdlib module RNG
    order = sorted(cfg.nodes, key=id)        # allocation-address order
    return t0, rng, jit, x, order


class Trace:
    def _jitter(self, start, nbytes):
        return 1.0 + 0.05 * self.rng.normal()    # long-lived generator
"""

DET_CLEAN = """\
import numpy as np


def _run_events(cfg, now):
    rng = np.random.default_rng(cfg.seed)
    order = sorted(cfg.nodes, key=lambda n: n.nid)
    draw = rng.standard_normal()
    return now + draw, order


def _jitter_mult(seed, start, nbytes):
    rng = np.random.default_rng((seed * 1000003) ^ nbytes)
    return 1.0 + 0.05 * rng.standard_normal()
"""

# The serving path's span recorder, as shipped, under a hot root that
# times its decode step through it: clean under every rule.
TRACING_SRC = (REPO / "src" / "repro" / "serving" / "tracing.py").read_text()
TRACED_STEP = """\
import jax.numpy as jnp

from repro.serving import tracing


def step(batch):
    with tracing.span("step"):
        with tracing.timed("decode_step") as sp:
            logits = jnp.ones((4, 8))
        if sp:
            sp.add(live=4)
    return logits, sp.seconds
"""
TRACED = {"serving/tracing.py": TRACING_SRC, "serving/engine.py": TRACED_STEP}

FIXTURES = {
    "host-sync": [
        (True, {"serving/engine.py": SYNC_BAD}),
        (False, {"serving/engine.py": SYNC_CLEAN}),
        (False, {"serving/engine.py": SYNC_LAUNDERED}),
        (False, TRACED),
    ],
    "clock-accounting": [
        (True, {"serving/billing.py": CLOCK_DEAD_T}),
        (True, {"serving/billing.py": CLOCK_DOUBLE}),
        (True, {"serving/wire.py": CLOCK_BACKWARDS}),
        (False, {"serving/runtime.py": CLOCK_CLEAN}),
        (False, TRACED),
    ],
    "units": [
        (True, {"serving/route.py": UNITS_MIX}),
        (False, {"serving/route.py": UNITS_CLEAN}),
        (False, TRACED),
    ],
    "kernel-contract": [
        (True, {"src/pkg/kernels/__init__.py": KC_INIT_BAD,
                "src/pkg/kernels/ref.py": KC_REF_BAD,
                "src/pkg/kernels/ops.py": KC_OPS_BAD,
                "tests/test_foo.py": "def test_nothing(): pass\n"}),
        (False, {"src/pkg/kernels/__init__.py": KC_INIT_OK,
                 "src/pkg/kernels/ref.py": KC_REF_OK,
                 "src/pkg/kernels/ops.py": KC_OPS_OK,
                 "tests/test_foo.py": KC_TEST_OK}),
        (False, TRACED),
    ],
    "ownership": [
        (True, {"serving/cluster.py": OWN_BAD}),
        (False, {"serving/cluster.py": OWN_CLEAN}),
        (False, TRACED),
    ],
    "determinism": [
        (True, {"serving/simulator.py": DET_BAD}),
        (False, {"serving/simulator.py": DET_CLEAN}),
        (False, TRACED),
    ],
}


def _write(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return tmp_path


def _run(tmp_path):
    return run_paths([str(tmp_path)], base=tmp_path)


@pytest.mark.parametrize(
    "rule_id,fires,files",
    [(rid, fires, files) for rid, cases in FIXTURES.items()
     for fires, files in cases],
    ids=[f"{rid}-{'fires' if fires else 'clean'}-{i}"
         for rid, cases in FIXTURES.items()
         for i, (fires, _) in enumerate(cases)])
def test_fixture(tmp_path, rule_id, fires, files):
    open_, _ = _run(_write(tmp_path, files))
    hits = [f for f in open_ if f.rule == rule_id]
    if fires:
        assert hits, f"{rule_id} did not fire on its bug fixture"
        for f in hits:   # findings are addressable and actionable
            assert f.path and f.line > 0 and f.message
    else:
        assert not hits, [f.render() for f in hits]


def test_no_dead_rules():
    """Meta-test: every registered rule has >=1 firing and >=1 clean
    fixture above — a rule nothing can trigger is dead weight."""
    assert {r.id for r in ALL_RULES} == set(FIXTURES)
    for rid, cases in FIXTURES.items():
        flags = {fires for fires, _ in cases}
        assert flags == {True, False}, f"{rid} lacks a fixture kind"


def test_rule_tokens_unique():
    tokens = [r.token for r in ALL_RULES]
    assert len(tokens) == len(set(tokens))


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------
def test_suppression_documents_finding(tmp_path):
    src = SYNC_BAD.replace(
        "    tok = np.asarray(jnp.argmax(logits, axis=-1))",
        "    # lint: sync-ok(fixture reason)\n"
        "    tok = np.asarray(jnp.argmax(logits, axis=-1))")
    open_, closed = _run(_write(tmp_path, {"serving/engine.py": src}))
    assert not any(f.rule == "host-sync" and "np.asarray" in f.message
                   for f in open_)
    doc = [f for f in closed if f.rule == "host-sync"]
    assert doc and doc[0].reason == "fixture reason"


def test_suppression_requires_reason(tmp_path):
    src = "import jax\n\ndef step(x):\n" \
          "    jax.block_until_ready(x)  # lint: sync-ok()\n"
    open_, _ = _run(_write(tmp_path, {"serving/engine.py": src}))
    assert any(f.rule == "lint-suppression" and "no reason" in f.message
               for f in open_)
    # ... and the empty suppression does NOT silence the finding
    assert any(f.rule == "host-sync" for f in open_)


def test_suppression_unknown_token(tmp_path):
    src = "def f():\n    return 1  # lint: bogus-ok(whatever)\n"
    open_, _ = _run(_write(tmp_path, {"serving/x.py": src}))
    assert any(f.rule == "lint-suppression" and "unknown" in f.message
               for f in open_)


def test_suppression_in_docstring_ignored(tmp_path):
    src = '"""Docs may show `# lint: sync-ok(reason)` freely."""\n' \
          "def f():\n    return 1\n"
    open_, _ = _run(_write(tmp_path, {"serving/x.py": src}))
    assert not open_


def test_parse_error_is_finding(tmp_path):
    open_, _ = _run(_write(tmp_path, {"serving/x.py": "def broken(:\n"}))
    assert any(f.rule == "parse-error" for f in open_)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_json_and_exit_codes(tmp_path, capsys, monkeypatch):
    _write(tmp_path, {"serving/engine.py": SYNC_BAD})
    monkeypatch.chdir(tmp_path)
    rc = main(["--format=json", "serving"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["counts"]["open"] >= 1
    assert all({"rule", "path", "line", "message", "hint"} <=
               set(f) for f in payload["findings"])

    _write(tmp_path, {"serving/engine.py": SYNC_CLEAN})
    rc = main(["--format=json", "serving"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["counts"]["open"] == 0


def test_cli_baseline_diff(tmp_path, capsys, monkeypatch):
    """--baseline diffs against a prior json report: pre-existing
    findings don't fail the run, new ones do, fixed ones count as
    resolved.  Identity is (rule, path, message) — line-number drift
    from unrelated edits must not resurrect old findings."""
    _write(tmp_path, {"serving/engine.py": SYNC_BAD})
    monkeypatch.chdir(tmp_path)
    main(["--format=json", "serving"])
    (tmp_path / "base.json").write_text(capsys.readouterr().out)

    # same tree vs its own report: green
    rc = main(["--format=json", "--baseline", "base.json", "serving"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["baseline"] == {"new": 0, "resolved": 0}

    # a NEW bug on top of the known ones: only it is reported, run fails
    _write(tmp_path, {"serving/engine.py": SYNC_BAD +
                      "\ndef run(state):\n"
                      "    jax.block_until_ready(state)\n"})
    rc = main(["--format=json", "--baseline", "base.json", "serving"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["baseline"]["new"] == len(payload["findings"]) == 1
    assert "run()" in payload["findings"][0]["message"]

    # everything fixed: green again, baseline findings counted resolved
    _write(tmp_path, {"serving/engine.py": SYNC_CLEAN})
    rc = main(["--format=json", "--baseline", "base.json", "serving"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["baseline"]["new"] == 0
    assert payload["baseline"]["resolved"] >= 1


# ---------------------------------------------------------------------------
# the real tree stays clean (the CI gate, enforced from the test suite too)
# ---------------------------------------------------------------------------
def test_repo_tree_is_clean():
    open_, closed = run_paths(
        [str(REPO / "src"), str(REPO / "benchmarks")], base=REPO)
    assert not open_, "\n".join(f.render() for f in open_)
    # every suppression in the tree carries a non-empty reason
    assert all(f.reason for f in closed)
    # the sanctioned decode-loop sync stays documented, not silenced
    assert any(f.path.endswith("serving/workers.py") and f.rule == "host-sync"
               for f in closed)
