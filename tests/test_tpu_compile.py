"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler is installed, so the tree kernel and the dense tree
lowering compile here for a v5e at the widths the chip smoke serves:
what Mosaic or XLA would refuse on the chip fails here at no chip time.
Nothing runs, so these say nothing about results or times.  The
topology is described only inside the fixture below: one process at a
time may load the TPU library, and it must not happen at import.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tree_gemm.tree_gemm import tree_gemm_pallas
from repro.ml.hummingbird import EnsembleGemm, predict_ensemble_gemm
from repro.train.loop import _TPU_OPTIONS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, n, f, t, i, l, o=1):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return dict(x=s((n, f)), a=s((t, f, i)), b=s((t, i)), c=s((t, i, l)),
                d=s((t, l)), e=s((t, l, o)), feat=s((t, i), jnp.int32))


@pytest.mark.parametrize("n,f,t,i,l", [
    (8192, 8, 8, 128, 128),          # the strategy calibration's forest
    (1 << 19, 7, 100, 1024, 1024),   # 100 depth-10 trees, one morsel
], ids=["calibration", "smoke_width"])
def test_tree_gemm_kernel_compiles_for_v5e(one_chip, n, f, t, i, l):
    s = _shapes(one_chip, n, f, t, i, l)
    compiled = jax.jit(tree_gemm_pallas).lower(
        s["x"], s["a"], s["b"], s["c"], s["d"], s["e"]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_tree_lowering_compiles_for_v5e(one_chip):
    n, f, t, i, l = 1 << 19, 7, 100, 1024, 1024
    s = _shapes(one_chip, n, f, t, i, l)

    def dense(x, b, c, d, e, feat):
        ens = EnsembleGemm(a=None, b=b, c=c, d=d, e=e, n_trees=t,
                           feat=feat)
        return predict_ensemble_gemm(ens, x)

    compiled = jax.jit(dense).lower(s["x"], s["b"], s["c"], s["d"],
                                    s["e"], s["feat"]).compile()
    mem = compiled.memory_analysis()
    # one morsel's working set stays well inside a v5e's 16 GB
    assert mem.temp_size_in_bytes < 8 << 30


def test_boosted_dense_lowering_compiles_whole_table_for_v5e(one_chip):
    """``flights_gbt.batch``'s program: 500 depth-8 trees padded to 200
    internal nodes and 208 leaves over the whole 483,000-row table in one
    call (no morsels), summed unaveraged, plus the base, then the
    sigmoid of ``PREDICT_PROBA``."""
    n, f, t, i, l = 483000, 7, 500, 200, 208
    s = _shapes(one_chip, n, f, t, i, l)

    def boosted(x, b, c, d, e, feat):
        ens = EnsembleGemm(a=None, b=b, c=c, d=d, e=e, n_trees=t,
                           average=False, feat=feat)
        return jax.nn.sigmoid(predict_ensemble_gemm(ens, x)[:, 0] - 1.25)

    compiled = jax.jit(boosted).lower(s["x"], s["b"], s["c"], s["d"],
                                      s["e"], s["feat"]).compile()
    # the whole-table working set a quarter of the HBM admits
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_train_step_compile_options_accepted_for_v5e(one_chip):
    """libtpu refuses a compile option it does not know, so an unknown key
    in the train loop's TPU options would fail the first step."""
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a: a @ a, compiler_options=_TPU_OPTIONS
                       ).lower(x).compile()
    assert compiled.as_text()


def test_compacted_dense_lowering_compiles_for_v5e(one_chip):
    """``los_rf.batch``'s model step behind its WHERE: 100 depth-10 trees
    over the live rows of one 262,144-row morsel, in chunks of
    ``COMPACT_ROWS`` (``codegen._score_live_rows``).  Its temporaries are
    sized by the chunk, so they come out under those of the same trees
    over every row of the morsel."""
    from repro.core.codegen import _score_live_rows
    n, f, t, i, l = 1 << 18, 6, 100, 1024, 1024
    s = _shapes(one_chip, n, f, t, i, l)
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)

    def trees(x, b, c, d, e, feat):
        ens = EnsembleGemm(a=None, b=b, c=c, d=d, e=e, n_trees=t,
                           feat=feat)
        return predict_ensemble_gemm(ens, x)

    def compacted(x, valid, b, c, d, e, feat):
        return _score_live_rows(lambda v: trees(v, b, c, d, e, feat), x,
                                valid)

    args = (s["b"], s["c"], s["d"], s["e"], s["feat"])
    live = jax.jit(compacted).lower(s["x"], valid, *args).compile()
    every = jax.jit(trees).lower(s["x"], *args).compile()
    assert live.memory_analysis().temp_size_in_bytes \
        < every.memory_analysis().temp_size_in_bytes
