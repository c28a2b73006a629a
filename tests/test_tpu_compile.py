"""Compile the serving path for a described TPU v5e — no chip needed.

The TPU compiler installed with jax compiles for a topology that is
described, not attached (``v5e:2x2``), and refuses what the chip would
refuse: blocks not aligned to the (8, 128) tiling, lowerings Mosaic lacks,
more VMEM than a kernel may use, programs larger than HBM. Interpret-mode
parity tests see none of that. Every Pallas kernel here compiles at the
widths of a SIFT1M deployment (M=16, G = Q*nprobe = 512, cap=1024,
nlist=4096, tile_n=1024; re-rank N=1M, D=128), and so does one whole fused
search pipeline at the Q=32 serving bucket.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist only the
worker that runs this file does so. The persistent compilation cache is off
around the compiles (their entries could not be read back without a chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import coarse as coarse_mod
from repro.core import ivf
from repro.core.lists import ListStore
from repro.core.pq import PQCodebook
from repro.engine import engine as engine_mod
from repro.kernels import fastscan_kernel as fk
from repro.kernels import ops
from repro.kernels import rerank_kernel as rk

M, G, CAP, NLIST, TILE_N = 16, 512, 1024, 4096, 1024
N, D, Q, NPROBE, K, RERANK = 1_000_000, 128, 32, 16, 10, 4
KC = RERANK * K
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name: str):
    """(fn, [(shape, dtype), ...]) for one kernel entry at real widths."""
    table = ((G, M, 16), jnp.uint8)
    store = ((NLIST, CAP, M // 2), jnp.uint8)
    probes = ((G,), jnp.int32)
    sizes = ((NLIST,), jnp.int32)
    fbits = ((G, CAP // 8), jnp.uint8)
    affine = ((G,), jnp.float32)
    flat_table = ((128, M, 16), jnp.uint8)
    flat_codes = ((65536, M // 2), jnp.uint8)
    topk = functools.partial(fk.fastscan_stream_topk_grouped, kc=KC,
                             tile_n=TILE_N, interpret=False)
    early = functools.partial(topk, early_exit=True, groups_per_query=NPROBE)
    cases = {
        "select": (functools.partial(fk.fastscan_select_tree_grouped,
                                     tile_n=TILE_N, interpret=False),
                   [table, ((G, CAP, M // 2), jnp.uint8)]),
        "mxu": (functools.partial(fk.fastscan_onehot_mxu_grouped,
                                  tile_n=TILE_N, interpret=False),
                [table, ((G, CAP, M // 2), jnp.uint8)]),
        "stream": (functools.partial(fk.fastscan_stream_grouped,
                                     tile_n=TILE_N, interpret=False),
                   [table, store, probes]),
        "stream_topk": (topk, [table, store, probes, sizes]),
        "stream_topk_filter": (
            lambda t, c, p, s, f: topk(t, c, p, s, filter_bits=f),
            [table, store, probes, sizes, fbits]),
        "stream_topk_early_exit": (
            lambda t, c, p, s, a, b: early(t, c, p, s, scales=a, biases=b),
            [table, store, probes, sizes, affine, affine]),
        "stream_topk_early_exit_filter": (
            lambda t, c, p, s, a, b, f: early(t, c, p, s, scales=a,
                                              biases=b, filter_bits=f),
            [table, store, probes, sizes, affine, affine, fbits]),
        "rerank": (functools.partial(rk.rerank_stream_topk, k=K, tile_r=64,
                                     interpret=False),
                   [((N, D), jnp.float32), ((Q, D), jnp.float32),
                    ((Q, 64), jnp.int32)]),
        "flat_select": (functools.partial(fk.fastscan_select_tree,
                                          tile_n=TILE_N, interpret=False),
                        [flat_table, flat_codes]),
        "flat_mxu": (functools.partial(fk.fastscan_onehot_mxu, tile_n=TILE_N,
                                       tile_q=128, interpret=False),
                     [flat_table, flat_codes]),
        "blockmin": (functools.partial(fk.fastscan_blockmin, tile_n=TILE_N,
                                       tile_q=128, interpret=False),
                     [flat_table, flat_codes]),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "select", "mxu", "stream", "stream_topk", "stream_topk_filter",
    "stream_topk_early_exit", "stream_topk_early_exit_filter", "rerank",
    "flat_select", "flat_mxu", "blockmin"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name)
    specs = [_spec(one_chip, shape, dtype) for shape, dtype in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_pipeline_compiles_for_v5e(one_chip, monkeypatch):
    """The whole serving program (coarse -> stream scan -> stream re-rank ->
    merge) at the Q=32 bucket over a SIFT1M-sized index, as the serving
    loop dispatches it: both kernels compiled in, and the program fits one
    chip's HBM."""
    # the engine picks interpret mode from the *attached* backend (the CPU
    # here); the compile targets the described chip, so take its branch
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cap = 4096  # the fullest of 1024 lists holding 1M skewed rows
    s = functools.partial(_spec, one_chip)
    coarse = coarse_mod.FlatCoarse(centroids=s((1024, D), jnp.float32))
    index = ivf.IVFIndex(
        centroids=s((1024, D), jnp.float32),
        codebook=PQCodebook(codewords=s((M, 16, D // M), jnp.float32)),
        lists=ListStore(codes=s((1024, cap, M // 2), jnp.uint8),
                        ids=s((1024, cap), jnp.int32),
                        sizes=s((1024,), jnp.int32)))
    lowered = engine_mod._fused_pipeline.lower(
        coarse, index, s((N, D), jnp.float32), None,
        s((Q, D), jnp.float32), None, None, None, None, k=K, nprobe=NPROBE,
        r=RERANK, scan_impl="stream", rerank_impl="stream", ef=64)
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
