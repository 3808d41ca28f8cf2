"""peritext-tpu: a TPU-native collaborative rich-text CRDT framework.

A brand-new implementation of the capabilities of Peritext (Ink & Switch's
rich-text CRDT; reference mounted at /root/reference) re-designed for TPU:

* :mod:`peritext_tpu.core` — scalar document oracle (the specification layer):
  full Micromerge semantics, changes/clocks, mark spans, patches, cursors.
* :mod:`peritext_tpu.ops` — packed document state and batched JAX/XLA kernels
  that apply (doc x op) tensors of CRDT operations across thousands of
  documents at once.
* :mod:`peritext_tpu.parallel` — replication: pubsub, change queues, vector
  clock anti-entropy, causal scheduling, and device-mesh sharding of the doc
  axis via jax.sharding.
* :mod:`peritext_tpu.api` — user-facing facades: single Doc, DocBatch (the TPU
  backend behind the InputOperation/Patch boundary), and the editor bridge.
* :mod:`peritext_tpu.store` — paged document storage: a device-resident
  global pool of fixed-size op pages + per-doc page tables behind
  ``layout="ragged"`` on DocBatch and ``layout="paged"``/``"ragged"`` on
  StreamingMerge (the padded layout stays the byte-equality oracle).
* :mod:`peritext_tpu.testing` — fuzz harness, trace replay, patch-accumulation
  oracle.
"""

from .core import (
    Change,
    CausalityError,
    Doc,
    Micromerge,
    Operation,
    PeritextError,
    span,
)
from .schema import ALL_MARKS, MARK_SPEC, MarkSchema, is_mark_type

__version__ = "0.1.0"

__all__ = [
    "Doc",
    "Micromerge",
    "Change",
    "Operation",
    "span",
    "PeritextError",
    "CausalityError",
    "MARK_SPEC",
    "MarkSchema",
    "ALL_MARKS",
    "is_mark_type",
    "__version__",
]
