"""Ahead-of-time compiles for a described TPU v5e, at the sizes the chip
runs (``chip_smoke.py``, the HMP cohort): the XLA entry points of the
main path and the four-chip paths.

No chip is needed: the TPU compiler compiles for a v5e:2x2 topology that
is described, not attached. A compile that passes is not a chip run; it
shows only that the chip's compiler accepts the program and that its
memory fits one chip. The topology is described inside a fixture — never
while a module is imported — and the tests skip where it cannot be.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.centering import center_distance_matrix_distributed
from repro.core.distance_matrix import DistanceMatrix
from repro.core.mantel import MantelStatistic, mantel_null_distributed
from repro.core.operators import (CenteredGramOperator,
                                  CondensedCenteredGramOperator)
from repro.dist.driver import _panel_stats
from repro.dist.metrics import get_metric
from repro.dist.tree import _tree_hoist
from repro.kernels.permute_reduce_ops import DEFAULT_CHUNK, _permute_reduce_jit
from repro.launch.mesh import chip_peaks
from repro.stats import engine
from repro.stats.permanova import PermanovaOperatorStatistic

N, D = 4096, 2048              # the library phase of chip_smoke.py
M = N * (N - 1) // 2
B = 32                         # the battery's permutation tile
K = 20                         # fsvd sketch width: 10 dimensions + 10
MULTI_N, MULTI_K = 2048, 1000  # the four-chip phase


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    def shape(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(
            topo.devices[0]))
    return shape


def _fits_one_chip(compiled, kind):
    ma = compiled.memory_analysis()
    used = ma.temp_size_in_bytes + ma.argument_size_in_bytes \
        + ma.output_size_in_bytes
    assert used < chip_peaks(kind)["hbm_bytes"], used


# --------------------------------------------------------------------------
# the default XLA entry points
# --------------------------------------------------------------------------
@pytest.mark.parametrize("s", [1, 2])
def test_permute_reduce_xla_compiles(topo, one_chip, s):
    compiled = _permute_reduce_jit.lower(
        one_chip((M,)), one_chip((s, M)), one_chip((B, N), jnp.int32),
        one_chip((M,), jnp.int32), one_chip((M,), jnp.int32),
        chunk=DEFAULT_CHUNK).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


@pytest.mark.parametrize("n", [N, 2898])       # chip_smoke; HMP16SData
def test_mantel_rows_null_distribution_compiles(topo, one_chip, n):
    """The row layout's whole test program (the TPU's Mantel path): the
    hoist's two squares and one draw's gathered rows fit one chip."""
    m = n * (n - 1) // 2
    stat = MantelStatistic(one_chip((m,)), None, n,
                           pre={"normxm": one_chip(()),
                                "ynorm": one_chip((m,))}, layout="rows")
    compiled = engine._null_distribution.lower(
        stat, one_chip((2,), jnp.uint32), permutations=999,
        batch_size=B).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


def test_panel_stats_xla_compiles(topo, one_chip):
    compiled = _panel_stats.lower(
        one_chip((256, D)), one_chip((N, D)),
        metric=get_metric("braycurtis"), feature_block=128).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


TIPS = 2048                    # a binary tree: 2·TIPS − 2 branches


def test_tree_hoist_compiles(topo, one_chip):
    branches = 2 * TIPS - 2
    compiled = _tree_hoist.lower(
        one_chip((N, TIPS)), one_chip((TIPS,), jnp.int32),
        one_chip((branches,), jnp.int32), one_chip((branches,), jnp.int32),
        one_chip((branches,))).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


def test_panel_stats_unifrac_compiles(topo, one_chip):
    """Production on the branch embedding: the ``unweighted_unifrac``
    product panel of ``_panel_stats`` at the HMP cohort's size (2,898
    samples, V3-V5's 90,764 branches), one product over the whole width.
    Its dot keeps float32: a DEFAULT-precision dot would round the
    branch lengths to bfloat16."""
    n, branches = 2898, 90764
    compiled = _panel_stats.lower(
        one_chip((256, branches)), one_chip((n, branches)),
        metric=get_metric("unweighted_unifrac"),
        feature_block=branches).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)
    dots = [line for line in compiled.as_text().splitlines()
            if re.search(r"= \S+ (dot|convolution)\(", line)]
    assert dots
    for line in dots:
        assert "operand_precision={highest,highest}" in line, line


def test_condensed_matvec_compiles(topo, one_chip):
    matvec = jax.jit(lambda dc, rm, gm, x: CondensedCenteredGramOperator(
        dc, rm, gm, N, 256).matvec(x))
    compiled = matvec.lower(one_chip((M,)), one_chip((N,)), one_chip(()),
                            one_chip((N, K))).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


def test_center_matvec_pallas_compiles(topo, one_chip):
    """The square operator's row-strip matvec at the HMP cohort's n (a
    ``Workspace`` over a distance matrix runs it for PCoA)."""
    n = 2898
    matvec = jax.jit(lambda d, rm, gm, x: CenteredGramOperator(
        d, rm, gm, n, 256).matvec(x))
    compiled = matvec.lower(one_chip((n, n)), one_chip((n,)), one_chip(()),
                            one_chip((n, K))).compile()
    _fits_one_chip(compiled, topo.devices[0].device_kind)


# --------------------------------------------------------------------------
# the four-chip paths (chip_smoke.py --chips 4)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def meshes(topo):
    devs = np.array(topo.devices)
    auto = jax.sharding.AxisType.Auto
    return {"line": jax.sharding.Mesh(devs.reshape(4), ("data",),
                                      axis_types=(auto,)),
            "square": jax.sharding.Mesh(devs.reshape(2, 2),
                                        ("data", "model"),
                                        axis_types=(auto, auto))}


def _on(mesh, shape, dtype=jnp.float32, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _spans_four_chips(compiled):
    shardings = jax.tree.leaves(compiled.output_shardings)
    assert any(len(s.device_set) == 4 for s in shardings)


def test_engine_permanova_four_chips_compiles(topo, meshes):
    n, m, line = MULTI_N, MULTI_N * (MULTI_N - 1) // 2, meshes["line"]

    def null(dc, rm, gm, grouping, key):
        op = CondensedCenteredGramOperator(dc, rm, gm, n, 256)
        stat = PermanovaOperatorStatistic(op, grouping, n, 3)
        return engine.null_distribution_distributed(stat, line, MULTI_K, key)

    compiled = jax.jit(null).lower(
        _on(line, (m,)), _on(line, (n,)), _on(line, ()),
        _on(line, (n,), jnp.int32), _on(line, (2,), jnp.uint32)).compile()
    _spans_four_chips(compiled)


def test_engine_mantel_four_chips_compiles(topo, meshes):
    n, m, line = MULTI_N, MULTI_N * (MULTI_N - 1) // 2, meshes["line"]

    def null(xc, normxm, ynorm, key):
        stat = MantelStatistic(xc, None, n,
                               pre={"normxm": normxm, "ynorm": ynorm},
                               layout="condensed")
        return engine.null_distribution_distributed(stat, line, MULTI_K, key)

    compiled = jax.jit(null).lower(
        _on(line, (m,)), _on(line, ()), _on(line, (m,)),
        _on(line, (2,), jnp.uint32)).compile()
    _spans_four_chips(compiled)


def test_mantel_distributed_four_chips_compiles(topo, meshes):
    n, square = MULTI_N, meshes["square"]

    def null(x, y, key):
        return mantel_null_distributed(
            DistanceMatrix(x, _skip_validation=True),
            DistanceMatrix(y, _skip_validation=True), square, MULTI_K, key)

    compiled = jax.jit(null).lower(
        _on(square, (n, n)), _on(square, (n, n)),
        _on(square, (2,), jnp.uint32)).compile()
    _spans_four_chips(compiled)
    assert "all-reduce" in compiled.as_text()        # the 'model' psum


def test_centering_distributed_four_chips_compiles(topo, meshes):
    square = meshes["square"]
    compiled = jax.jit(
        lambda d: center_distance_matrix_distributed(d, square)).lower(
        _on(square, (MULTI_N, MULTI_N), spec=P("data", "model"))).compile()
    _spans_four_chips(compiled)
