"""Plain float32 references, one per model family, named by the configurations.

A configuration's ``reference`` key names a module here,
``bench/reference/<name>.py``, which ``bench.harness.load_reference`` loads.
It imports nothing of the program under test and makes its weights from
the seed itself. ``model`` below is the configuration's ``model`` dict as
run (the program's ``ModelConfig`` fields). The module exposes:

``logit_gaps(model, seed, tokens, targets, modes)``
    The served tokens' gaps below the float32 reference's best logit, and
    the control's (``modes`` beyond ``"f32"``): what a run compares
    (``bench/serve.py``) and what ``bench/control.py`` reads.
``logits(model, seed, tokens)``
    float32 logits (B, S, vocab) of int32 tokens (B, S), each row a
    sequence from position 0: for the agreement test with the program's
    forward pass on the CPU.
``weight_pairs(model, seed, params)``
    Yields ``(name, reference array, program array)`` for each matrix the
    reference draws from the seed, beside the program's initialised
    ``params``: for the test that both draw the same weights.
``serve_flops(model, tokens, pairs)``
    Model FLOPs of a served forward over ``tokens`` positions attending
    ``pairs`` query-key pairs in all: 2 per matmul parameter a token is
    multiplied by on this chip, plus the attention work of each pair. Read
    by ``bench/metrics/mfu.serve.py``.
``cache_bytes(model, kv_tokens)``
    Bytes of cached state that attention reads for ``kv_tokens`` keys over
    all layers. Read by ``bench/metrics/paged_attn_roofline.py``.

Optional:

``compile_probes(model, rows)``
    Yields ``(program name, function, *argument shapes)`` for the programs
    a run at ``rows`` positions a row compiles, so that
    ``bench/compile_check.py`` can size them for a described chip.
``WEIGHT_RTOL``, ``LOGITS_TOL``
    A tolerance of the two tests above other than their defaults (weights
    ``rtol=3e-7, atol=0``; logits ``rtol=atol=2e-4``), stated with the
    reason beside it.
"""
