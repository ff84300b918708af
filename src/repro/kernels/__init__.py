"""Pallas TPU kernels for the perf-critical compute layers.

flash_attention — causal GQA flash attention (VMEM tiles, MXU-aligned)
ssd_scan        — Mamba2 SSD chunked scan (state carried in VMEM scratch)
rmsnorm         — fused norm
embedding_bag   — pooled DLRM lookups (explicit-DMA gather)

ops.py: jit'd wrappers; the caller picks kernel or oracle (``impl=``) and
        native or interpreted Pallas (``interpret=``).
ref.py: pure-jnp oracles for the allclose tests.
"""

from repro.kernels import ops, ref  # noqa: F401
