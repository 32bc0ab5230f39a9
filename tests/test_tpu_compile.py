"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so each program of the served path
is compiled here at real widths for a ``v5e:2x2`` topology: what Mosaic or
XLA would refuse on the chip (unaligned tiles, too much VMEM, HBM
overflow) fails here.  Nothing runs, so nothing is timed or checked for
results.  The topology is described inside a fixture, never at import: a
process that loads the TPU library holds it until it exits.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.cluster_index import SEARCH_STAGES, device_search_batch
from repro.core.distributed import sharded_search_step
from repro.core.pq import default_pq_dims
from repro.kernels import distance, fused_topk, ops, pq_adc

#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30
#: the chip benchmark's layouts (lists, slots, D) and their batch sizes
DEEP_LAYOUT, GIST_LAYOUT = (12_500, 1_536, 96), (1_000, 1_792, 960)
BULK = [(*DEEP_LAYOUT, 256), (*GIST_LAYOUT, 16)]
BULK_IDS = ["deep96-f32", "gist960-f32"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # the TPU compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep the persistent cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_search(one_chip):
    """Compiles ``device_search_batch`` at (lists, slots, D, B), nprobe 32,
    k 10, once a shape.  Off the TPU the kernels would be compiled for the
    Pallas interpreter: Mosaic is asked for here."""
    ops.set_default_interpret(False)
    done = {}

    def compile_at(L, maxlen, D, B):
        if (L, maxlen, D, B) not in done:
            # a fresh callable, so no trace made for the interpreter is reused
            fn = jax.jit(functools.partial(device_search_batch, nprobe=32,
                                           k=10))
            done[L, maxlen, D, B] = fn.lower(
                _sds((L, D), jnp.float32, one_chip),
                _sds((L, maxlen, D), jnp.float32, one_chip),
                _sds((L, maxlen), jnp.int32, one_chip),
                _sds((B, D), jnp.float32, one_chip)).compile()
        return done[L, maxlen, D, B]
    yield compile_at
    ops.set_default_interpret(None)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dim,dtype", [(96, jnp.float32), (960, jnp.float32),
                                       (100, jnp.int8)])
def test_l2_distance_compiles(one_chip, dim, dtype):
    c = distance.l2_distance.lower(_sds((64, dim), dtype, one_chip),
                                   _sds((4096, dim), dtype, one_chip)
                                   ).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("dim", [96, 960])
def test_batched_l2_topk_compiles(one_chip, dim):
    # the tiles repro.exec.batched dispatches: 8 queries x 128 candidates
    c = fused_topk.l2_topk.lower(
        _sds((64, dim), jnp.float32, one_chip),
        _sds((4096, dim), jnp.float32, one_chip), 10,
        block_q=8, block_n=128).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("m", [8, 16, default_pq_dims(960)])
def test_adc_lookup_compiles(one_chip, m):
    c = pq_adc.adc_lookup.lower(_sds((16384, m), jnp.uint8, one_chip),
                                _sds((m, 256), jnp.float32, one_chip)
                                ).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("maxlen", [640, 616])
def test_device_search_batch_compiles_at_deep_1m(compile_search, maxlen):
    # the layout chip_smoke.py builds from the DEEP-shaped 1M corpus at 1%
    # centroids: 15,491 lists, the longest 616 after closure replication,
    # padded to 640 slots; and unpadded, which the list scan pads itself
    c = compile_search(15_491, maxlen, 96, 64)
    assert _has_kernel(c)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("L,maxlen,D,B", BULK, ids=BULK_IDS)
def test_device_search_batch_scans_lists_in_place(compile_search,
                                                  L, maxlen, D, B):
    # the list-scan kernel reads the resident lists: no (B, nprobe, slots,
    # D) copy and no relayout of the corpus takes device memory
    c = compile_search(L, maxlen, D, B)
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("B", [1, 2, 4, 8, 16, 32, 64])
def test_device_search_batch_compiles_online_shapes(compile_search, B):
    # the online cell's batches, padded to powers of two, on DEEP's layout
    c = compile_search(*DEEP_LAYOUT, B)
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < 2**30


def _first_scopes(hlo_text: str) -> list:
    """For every ``op_name`` of the form ``jit(...)/...``, the element after
    the ``jit(...)``, or None where a primitive's name is all that follows."""
    out = []
    for op_name in re.findall(r'op_name="(jit\([^"]*)"', hlo_text):
        parts = op_name.split("/")
        out.append(parts[1] if len(parts) > 2 else None)
    return out


@pytest.mark.parametrize("L,maxlen,D,B", BULK, ids=BULK_IDS)
def test_device_search_batch_names_its_stages(compile_search, L, maxlen, D, B):
    # the layouts of the chip benchmark's cells, at their batch sizes: every
    # op the TPU compiler keeps metadata for is in one of the four stages
    text = compile_search(L, maxlen, D, B).as_text()
    scopes = set(_first_scopes(text))
    assert scopes == set(SEARCH_STAGES), sorted(map(str, scopes))


@pytest.mark.parametrize("nprobe_local,batch", [(16, 64), (976, 4)])
def test_sharded_search_step_compiles_on_4_chips(topo, nprobe_local, batch):
    # chip_smoke.py --chips 4: 1M DEEP vectors in 3904 lists over 4 chips,
    # the longest 2253; 976 = every local list
    mesh = jax.sharding.Mesh(topo.devices[:4], ("shard",))
    shard, repl = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
    L, M, D = 3904, 2253, 96
    fn = jax.jit(sharded_search_step(mesh, nprobe_local=nprobe_local, k=10))
    c = fn.lower(_sds((L, D), jnp.float32, shard),
                 _sds((L, M, D), jnp.float32, shard),
                 _sds((L, M), jnp.int32, shard),
                 _sds((L, M), jnp.float32, shard),
                 _sds((batch, D), jnp.float32, repl)).compile()
    text = c.as_text()
    assert "all-gather" in text
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
