"""What every architecture's counts share: the bytes of the served type
and the chip's published peaks.

The operations and bytes of a model's programs are worked out from its
shapes by its module in ``bench/archs/`` (``param_count``,
``prefill_flops``, ``decode_flops``, ``decode_bytes``, ``handoff_bytes``);
the metric readers reach them as ``ctx.counts``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

BF16 = 2
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak_for(kind: str) -> Dict:
    """The chip's published peaks (``peaks.json``) for a JAX
    ``device_kind``; a kind that is not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]
