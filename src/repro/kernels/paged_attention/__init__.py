# Fused Pallas paged-decode attention (DESIGN.md §16):
#
#   kernel.py   — pl.pallas_call walking (block table, last-page length)
#                 per row: one-pass online-softmax attention, whole
#                 pages DMA'd from HBM, GQA-grouped queries,
#                 sentinel-masked pages contribute nothing. Interpret
#                 tier is the CI-gated surface; compiled on a TPU.
#   ops.py      — model-facing wrapper: [B,1,Hq,hd] decode shapes in and
#                 out, head-pad handling.
#   ref.py      — self-contained pure-jnp gather-then-attend oracle for
#                 the differential suite (tests/test_paged_attention.py).
#   dispatch.py — bucketed compiled-dispatch cache (hyadmin DecodeRunner
#                 idiom): pow-2 occupancy buckets via WindowedPlanner +
#                 the trace ledger proving rounds never retrace.
#
# The serving consumer is SlotServeEngine(attention_impl="fused");
# models/attention.py::paged_decode_attention routes here on impl="fused"
# and keeps the gather path as the executable reference.

from repro.kernels.paged_attention.kernel import (  # noqa: F401
    fused_paged_decode,
)
from repro.kernels.paged_attention.ops import (  # noqa: F401
    paged_decode_fused,
)
from repro.kernels.paged_attention.ref import (  # noqa: F401
    paged_decode_ref,
    row_live,
)
