"""A stand-in architecture module for the harness's seam test: every name
of the contract, each giving a number that tells the test it was called.

``check`` reports every ``used`` key that starts with ``differs_``;
``Reference.gaps`` gives every served token the gap ``seed / 1000`` (its
control one more); the counts are fixed multiples of their arguments.
"""
import numpy as np


def check(cfg, used, arch):
    return {k: (None, v) for k, v in used.items() if k.startswith("differs_")}


class Reference:
    def __init__(self, model, arch, seed):
        self.gap = seed / 1000

    def gaps(self, served, control=False):
        gaps = [np.full(len(r.tokens), self.gap) for r in served]
        return gaps, ([g + 1 for g in gaps] if control else None)


def param_count(m):
    return 11


def prefill_flops(m, prompt):
    return 13 * prompt


def decode_flops(m, positions):
    return 17 * len(positions)


def decode_bytes(m, positions):
    return 19 * len(positions)


def handoff_bytes(m, prompt):
    return 23 * prompt + 29


def warm_handoff(cfg, prompt):
    shape = (1, 1, prompt, 2)
    return np.zeros(shape, np.float32), np.ones(shape, np.float32)
