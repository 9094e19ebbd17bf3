"""Ahead-of-time compiles for a described TPU v5e chip.

Nothing runs and no chip is needed: the TPU compiler installed with JAX
compiles for a chip that is described, not attached.  Mosaic refuses what
the Pallas interpreter accepts (vector gathers and scatters, unaligned
slices, too much VMEM), so these compiles guard the serving kernels and
the four endpoint programs between chip runs:

* both serving kernels through their ``ops`` wrappers (``interpret=None``
  picks the compiled TPU branch when lowering for TPU), at the tables of
  ``chip_smoke.py``'s index and at tables that fill the VMEM budgets;
* ``plan`` / ``list`` / ``topk`` / ``tfidf`` from
  ``RetrievalService.endpoint_program`` with both kernels selected.

The topology is described inside a module-scoped fixture (never at
import), and the persistent compile cache is off around the compiles: an
entry written for a described chip cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.collections import SyntheticSpec, generate
from repro.kernels import ops
from repro.serve.retrieval import RetrievalService

MOSAIC = 'custom_call_target="tpu_custom_call"'

#: tables of the chip smoke's index (version-p001 at scale 2: n = 800,400,
#: d = 400, sigma = 5 -> 3 wavelet levels of 25,014 words; 15,937 ILCP runs
#: -> a 14-level RMQ table) and tables that fill each 12 MiB budget
SEARCH_SIZES = {"smoke": dict(words=25_014, batch=128, max_m=8),
                "budget": dict(words=524_000, batch=256, max_m=512)}
LIST_SIZES = {"smoke": dict(runs=15_937, levels=14, n=800_400, d=400),
              "budget": dict(runs=50_000, levels=16, n=2_000_000, d=4_000)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _mosaic_launches(fn, args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(MOSAIC)


@pytest.mark.parametrize("size", sorted(SEARCH_SIZES))
def test_backward_search_kernel_compiles(one_chip, size):
    p = SEARCH_SIZES[size]
    levels, words, sigma = 3, p["words"], 5
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    assert ops.backward_search_fits(jnp.zeros((levels, words), jnp.uint32),
                                    jnp.zeros((levels, words), jnp.int32))
    fn = functools.partial(ops.backward_search, n=32 * words, sigma=sigma)
    args = (shape((levels, words), jnp.uint32), shape((levels, words), jnp.int32),
            shape((levels,), jnp.int32), shape((sigma,), jnp.int32),
            shape((p["batch"], p["max_m"]), jnp.int32),
            shape((p["batch"],), jnp.int32))
    assert _mosaic_launches(fn, args) == 1


@pytest.mark.parametrize("size", sorted(LIST_SIZES))
def test_ilcp_list_kernel_compiles(one_chip, size):
    p = LIST_SIZES[size]
    runs, levels, n, batch = p["runs"], p["levels"], p["n"], 64
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    assert ops.ilcp_list_fits(jnp.zeros(runs), jnp.zeros((levels, runs)),
                              jnp.zeros(runs + 1), jnp.zeros(n), d=p["d"])
    fn = functools.partial(ops.ilcp_list, d=p["d"], max_df=256)
    args = (shape((runs,), jnp.int32), shape((levels, runs), jnp.int32),
            shape((runs + 1,), jnp.int32), shape((n,), jnp.int32),
            shape((batch,), jnp.int32), shape((batch,), jnp.int32))
    assert _mosaic_launches(fn, args) == 1


@pytest.fixture(scope="module")
def svc():
    coll = generate(SyntheticSpec("version", n_base=2, n_variants=6,
                                  base_len=80, mutation_rate=0.01, seed=3))
    return RetrievalService.build(coll, block_size=16, beta=8.0,
                                  validate=False)


@pytest.mark.parametrize("kind,launches", [
    ("plan", 1), ("list", 2), ("topk", 1), ("tfidf", 1),
])
def test_endpoint_program_compiles(one_chip, svc, kind, launches):
    fn, build_args = svc.endpoint_program(kind, use_kernel=True,
                                          use_list_kernel=True)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=one_chip),
        build_args(8, 8),
    )
    assert _mosaic_launches(fn, args) == launches
