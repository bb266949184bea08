"""Hand-written TPU kernels (Pallas) for the ops XLA cannot fuse well.

The reference framework's analog is its hand-tuned kernel layer —
`operators/math/` CUDA kernels and the xbyak JIT (`operators/jit/`,
SURVEY §2.6).  On TPU the op set that needs hand kernels is different:
attention at long sequence length (memory-bound softmax materialization)
is the dominant one, so this package provides

- :func:`flash_attention` — fused online-softmax attention, Pallas on TPU
  (MXU-tiled, O(T) memory), blockwise-``lax.scan`` JAX fallback elsewhere;
- :func:`ring_attention` — sequence-parallel attention over a mesh axis:
  KV blocks rotate around the ``sp`` ring via ``lax.ppermute`` while each
  step's partials merge with the running online softmax.  This is the
  long-context capability the 2019 reference lacks entirely (SURVEY §5.7)
  and the replacement for its LoD ``sequence_ops`` machinery.
"""

from .flash_attention import flash_attention, mha_reference  # noqa
from .ring_attention import ring_attention  # noqa
