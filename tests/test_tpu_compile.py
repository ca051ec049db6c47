"""The query path's Pallas kernels compile for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``) cannot see what the chip's
compiler refuses: unaligned block shapes, reductions Mosaic does not
lower, unsupported casts.  These tests compile each kernel the query path
reaches at real widths for one chip of a described ``v5e:2x2`` topology —
nothing runs — and look for the Mosaic kernel (``tpu_custom_call``) in the
compiled text, of each kernel alone and of whole plan fixpoints.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sparql
from repro.core.graph import DENSE_ADJ_MAX_BYTES
from repro.data import synth
from repro.engine.batcher import DEFAULT_BUCKETS
from repro.engine.plan import CompiledPlan
from repro.engine.template import canonicalize
from repro.kernels.bitmm import kernel as bitmm_kernel
from repro.kernels.segsum import kernel as segsum_kernel

# largest node count whose dense [n, n] plane fits the dense-tier budget
DENSE_N = math.isqrt(DENSE_ADJ_MAX_BYTES)
# the LUBM corpus of 10,000 universities the edge tier serves on one chip
EDGE_N = 1_650_000
# edge blocks of one operator there: every 256-node destination window gets
# at least one block (~6.4k), and memberOf's ~800k edges split into more
EDGE_BLOCKS = 8192
# chi rows of the serving template (3 variables) at the largest bucket
V = 3 * max(DEFAULT_BUCKETS)
QUERY = "{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_bitmm_apply_packed_compiles_at_dense_budget(one_chip):
    nw = -(-DENSE_N // 32)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        bitmm_kernel.bitmm_apply_packed,
        sds((V, nw)), sds((DENSE_N, nw)), sds((V, V)),
    )
    assert "tpu_custom_call" in text


def test_bitmm_packed_compiles_at_dense_budget(one_chip):
    nw = -(-DENSE_N // 32)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        bitmm_kernel.bitmm_packed, sds((V, DENSE_N)), sds((DENSE_N, nw))
    )
    assert "tpu_custom_call" in text


def test_segor_blocks_compiles_at_lubm_scale(one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda vb, sb, win: segsum_kernel.segor_blocks(
            vb, sb, win, num_segments=EDGE_N
        ),
        sds((EDGE_BLOCKS, 256, V), jnp.int8),
        sds((EDGE_BLOCKS, 256), jnp.int32),
        sds((EDGE_BLOCKS,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "engine", ["packed_fused", "packed", "sparse", "jacobi_packed"]
)
def test_plan_fixpoint_calls_the_kernel(engine, one_chip, monkeypatch):
    """A whole jitted plan fixpoint, as a TPU process builds it, holds the
    Mosaic kernel.  The plan picks its lowering from the default backend,
    so the test reports a TPU while the plan is built and lowered."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    graph = synth.lubm_like(n_universities=3, seed=0)
    plan = CompiledPlan(
        canonicalize(sparql.parse(QUERY)).template, graph,
        engine=engine, batch=2,
    )
    consts = canonicalize(sparql.parse(QUERY)).constants
    inputs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        plan.fixpoint_inputs([consts] * plan.batch),
    )
    text = plan.fixpoint.lower(*inputs).compile().as_text()
    assert "tpu_custom_call" in text
