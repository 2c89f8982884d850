#!/usr/bin/env python3
"""Drive a whole benchmark run with the timed path broken underneath.

    python3 bench/tests/fault_run.py FAULT [bench/run.py arguments]

FAULT is one of:

* ``token``: a token altered where it is produced: each request's tenth
  generated token is replaced by its neighbour in the vocabulary, and the
  request decodes on from it;
* ``state``: the decode step returns its arena unchanged, so no generated
  token's keys and values are ever kept;
* ``handoff``: the prompt's restored keys and values never reach the
  arena (the injection into a slot is dropped).

The program is patched in this process only; the harness runs as it does
on the chip (with ``--rehearse`` it takes the reduced configuration on the
CPU), and its last line must read ``"correct": false``.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402  (bench/run.py)
from repro.serving import workers  # noqa: E402

FAULTS = ("token", "state", "handoff")


def break_program(fault: str) -> None:
    Worker = workers.DecodeWorker
    if fault == "token":
        produce = Worker.decode_iteration

        def decode_iteration(self, active):
            wall = produce(self, active)
            for slot in active:
                if len(slot.toks) == 11:
                    t = (slot.toks[-1] + 1) % self.model.cfg.vocab_size
                    slot.toks[-1] = t
                    self._last_tok[slot.idx] = t
            return wall
        Worker.decode_iteration = decode_iteration
    elif fault == "state":
        arena_fn = Worker._arena_fn

        def stale_arena_fn(self):
            step = arena_fn(self)

            def stale(params, arena, *rest):
                nxt, _ = step(params, arena, *rest)
                return nxt, arena
            return stale
        Worker._arena_fn = stale_arena_fn
    elif fault == "handoff":
        def inject_restored(self, kv, idx):
            self.ensure_arena()
        Worker.inject_restored = inject_restored
    else:
        raise SystemExit(f"unknown fault {fault!r}; one of {FAULTS}")


if __name__ == "__main__":
    break_program(sys.argv[1])
    sys.exit(run.main(sys.argv[2:]))
