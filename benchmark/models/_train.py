"""What the training adapters share: the executor on the right place, the
data-parallel wrapper, and a resident ring of batches."""

import numpy as np


def executor(on_chip):
    import paddle_tpu as pt
    return pt.Executor(pt.TPUPlace(0)) if on_chip else pt.Executor()


def maybe_data_parallel(main, loss, chips):
    """The program itself on one chip; on several, the same program under
    ``CompiledProgram.with_data_parallel`` over all of them."""
    if chips == 1:
        return main
    import paddle_tpu as pt
    return pt.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=chips)


def put_ring(batches, chips):
    """Device-resident copies of the host batches; on several chips each is
    laid out over the data-parallel mesh as the compiled step wants it, so
    that no step moves its input."""
    import jax
    if chips == 1:
        return [{k: jax.device_put(v) for k, v in b.items()} for b in batches]
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import data_parallel_mesh
    sh = NamedSharding(data_parallel_mesh(chips), P("dp"))
    return [{k: jax.device_put(v, sh) for k, v in b.items()}
            for b in batches]


def rel_err(got, want):
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-12)


def rng_of(seed, salt=0):
    return np.random.RandomState((int(seed) + salt) % (2 ** 32))
