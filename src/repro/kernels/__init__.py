"""Pallas TPU kernels — the LARGE-tile operator bitstreams.

TPU (v5e) is the target.  Each kernel takes ``interpret=None`` by default,
which :func:`interpret_mode` resolves when the kernel is traced: compiled
for the chip when JAX's default backend is a TPU, interpreted (the kernel
body runs as plain JAX ops) on any other backend.  Nothing is decided at
import, so importing ``repro`` never initialises a backend.

Kernel inventory (one module per compute hot-spot, each with a pure-jnp
oracle in ``ref.py`` and a jitted public wrapper in ``ops.py``):

  vmul_reduce     — the paper's own evaluation pattern (Σ A⃗·B⃗), fused
  rmsnorm         — fused RMSNorm (row-blocked)
  flash_attention — blocked online-softmax attention (causal, GQA)
  ssd_scan        — Mamba-2 SSD chunk-local kernel (intra-chunk quadratic part)

Importing ``repro.kernels.ops`` (or calling :func:`register_overlay_bitstreams`)
self-registers these kernels in the overlay's trace frontend
(``patterns.register_call``): a traced user function calling e.g.
``ops.vmul_reduce`` lowers to ONE LARGE-tile node — the pre-synthesized
Pallas bitstream — instead of being decomposed into scalar primitives.
"""

import jax


def register_overlay_bitstreams() -> None:
    """Idempotently register the Pallas kernels as overlay LARGE operators."""
    from repro.kernels import ops  # noqa: F401  — import side effect registers


def interpret_mode(interpret: bool | None) -> bool:
    """The ``interpret`` flag of one ``pallas_call``, decided at trace time:
    an explicit value wins, else compile on a TPU and interpret elsewhere."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


# MXU/VPU alignment constants (v5e): 128-lane registers, 128x128 systolic array.
LANE = 128
SUBLANE = 8
