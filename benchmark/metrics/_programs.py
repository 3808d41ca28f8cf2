"""Device programs by the jit names the trace prints for them today."""

APPLY = ("jit_apply_batch",)           # ops/kernel.py apply_batch(_jit)
RESOLVE = ("jit_resolve",)             # ops/resolve.py resolve_jit
ROUNDS = ("stacked_rounds", "staged_rounds", "jit_apply_batch")  # streaming commits
