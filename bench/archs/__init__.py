"""One module per architecture: its plain reference, its counts and its
size check.

A configuration file (``bench/configs/<name>.json``) names its module
under the key ``"harness"``; ``bench/run.py`` loads
``bench/archs/<harness>.py`` by path and reaches the architecture only
through it, so the harness, the metric readers and ``BENCHMARK.json``
stay the same for every architecture.  Adding one is a new module here
and a new configuration file that names it.

A module defines every name in :data:`CONTRACT`:

``check(cfg, used: dict, arch: dict) -> dict``
    The mismatches between the program's registry entry ``cfg`` and the
    file's ``used`` (or ``rehearsal``) sizes and ``architecture`` block,
    as ``{key: (program's value, file's value)}``; empty when they agree.

``Reference(model: dict, arch: dict, seed: int)``
    The plain float32 reference, importing nothing of the program and
    redrawing the weights from the seed (``bench/weights.py``).  Its
    ``.gaps(served, control=False)`` takes a list of
    :class:`reference.Served` requests of one prompt and output length and
    returns ``(gaps, control_gaps)``: per request, one gap per served
    token, the reference's best logit minus that of the token served;
    with ``control``, minus that of the token the reference in the next
    lower precision ranks first there (else None).

``param_count(m)``, ``prefill_flops(m, prompt)``,
``decode_flops(m, positions)``, ``decode_bytes(m, positions)``
    Parameters held; operations of one batch-1 prefill of ``prompt``
    tokens; operations and bytes of one decode step over live slots whose
    new tokens sit at ``positions``.  ``m`` is the file's ``used`` block
    merged with its ``architecture`` block (``Cell.model``).

``handoff_bytes(m, prompt)``
    The uncompressed hand-off payload of one request with a ``prompt``
    of that many tokens: the prompt's keys and values, plus any state
    whose size does not depend on ``prompt`` (a recurrent layer's).

``warm_handoff(cfg, prompt) -> (k, v)``
    Host arrays in the form of one request's hand-off of a ``prompt``-token
    prompt, as the program's ``KVCache`` holds it (float32, drawn from a
    fixed seed): the warm-up injects them into every arena slot, so that
    the window compiles no injection.  ``cfg`` is the program's registry
    entry.

Modules import what every architecture shares from ``reference``
(``Served``, the strategies' ``restore``, ``handed_off``, ``to_fp8``,
``_mm``, ``_rms``, ``_rope``), ``counts`` (``BF16``) and ``weights``
(``draw_leaf``).
"""

CONTRACT = ("check", "Reference", "param_count", "prefill_flops",
            "decode_flops", "decode_bytes", "handoff_bytes", "warm_handoff")
