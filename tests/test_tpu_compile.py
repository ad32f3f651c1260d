"""The 128-bit decimal arithmetic compiles for a TPU v5e, without one:
the chip's compiler is installed here and compiles for a described
chip.  It refuses what interpret-mode and CPU runs cannot show (64-bit
integer ops the X64 rewriter lacks, a program too large for the chip).
Nothing runs: results are `tests/test_decimal128.py`'s."""

import os

import pytest

from spark_rapids_tpu import types as T

ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    import jax
    import jax.numpy as jnp

    args = [jax.ShapeDtypeStruct(s, jnp.int64, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_q1_charge_and_its_sum_compile(one_chip):
    """q1's `price * (1 - disc) * (1 + tax)` over DECIMAL(15,2) cents,
    decimal(32,4) then decimal(38,6) with its overflow check, summed
    as split terms and settled: a 2^20-row batch."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import int128 as W

    n15, n16 = T.DecimalType(15, 2), T.DecimalType(16, 2)
    t32, t38 = T.DecimalType(32, 4), T.DecimalType(38, 6)

    def charge_sum(price, disc, tax):
        x, _ = W.multiply(price, n15, 100 - disc, n16, t32)
        y, ok = W.multiply(x, t32, 100 + tax, n16, t38)
        terms = W.sum_terms(y, t38, ok, False)
        return W.settle([jnp.sum(t)[None] for t in terms], t38, False)

    compiled = _compile(charge_sum, one_chip, (ROWS,), (ROWS,), (ROWS,))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_average_finish_and_rescale_compile(one_chip):
    """`avg`'s finish (a 128-bit sum over a 64-bit count, two HALF_UP
    roundings) and a scale cut by `allowPrecisionLoss`."""
    from spark_rapids_tpu.exprs.base import BoundReference, EvalContext
    from spark_rapids_tpu.exprs.decimal import DecimalAverage
    from spark_rapids_tpu.ops import int128 as W

    sum_t = T.DecimalType(25, 2)
    avg = DecimalAverage(BoundReference(0, sum_t, True, "s"),
                         BoundReference(1, T.LONG, True, "n"),
                         T.DecimalType(19, 6))

    def finish(total, count):
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import Column

        import jax.numpy as jnp

        valid = jnp.ones(count.shape, bool)
        batch = ColumnarBatch([Column(total, valid, sum_t),
                               Column(count, valid, T.LONG)],
                              count.shape[0],
                              T.Schema([T.Field("s", sum_t),
                                        T.Field("n", T.LONG)]))
        out = avg.eval(EvalContext.for_batch(batch))
        cut = W.rescaled(total, T.DecimalType(38, 10),
                         T.DecimalType(38, 2))
        return out.data, out.validity, cut

    _compile(finish, one_chip, (8, 2), (8,))
