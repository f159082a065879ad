"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached: the TPU compiler that ships with JAX compiles for a
described ``v5e:2x2`` and refuses what the chip would refuse (unaligned
blocks, casts the lowering lacks, gathers it cannot vectorize, programs that
overflow HBM), which interpret mode never does. Shapes are those of
``chip_smoke.py``'s WHYPE cell: C=102,400 classes over 1,024 cores (100 per
core), d=2048 (64 words), 256 trials per standalone serve, 2 ring slots of
512 trials over 8 resident tenants.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test collection must
not depend on which worker got it.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

C, RX, D, W = 102_400, 1024, 2048, 64
C_CORE = C // RX
TRIALS, SLOTS, TENANTS = 256, 2, 8
RING_TRIALS = 512
K_MAX = 64  # sparse index lists at the 1/32 wire-parity density of d=2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_case(name, sds):
    """(fn, arg structs) of one kernel at the chip_smoke shapes."""
    from repro.kernels.assoc_matmul import assoc_matmul
    from repro.kernels.hamming import (
        hamming_search,
        hamming_search_banked,
        hamming_topk_banked,
    )
    from repro.kernels.majority import majority_bundle
    from repro.kernels.sparse import sparse_search, sparse_topk_banked

    u32, i32, u8 = jnp.uint32, jnp.int32, jnp.uint8
    ring_q = sds((SLOTS * RX, RING_TRIALS, W), u32)
    table = sds((TENANTS * RX, C_CORE, W), u32)
    rows = sds((SLOTS * RX,), i32)
    banks = sds((RX, C_CORE, W), u32)
    if name == "hamming_topk_banked_k1":
        # the ring's search: every (slot, core) bank through the row table
        return (lambda q, p, r: hamming_topk_banked(
            q, p, bank_rows=r, interpret=False), (ring_q, table, rows))
    if name == "hamming_topk_banked_k1_table1":
        # Table I's ring: 32 slots x 64 cores of 64 trials, d=512, several
        # small banks a grid step
        return (lambda q, p, r: hamming_topk_banked(
            q, p, bank_rows=r, interpret=False),
            (sds((32 * 64, 64, 16), u32), sds((64 * 64, C_CORE, 16), u32),
             sds((32 * 64,), i32)))
    if name == "bsc_bitplane_copies":
        # the ring's RX fan-out: every (slot, core)'s bitplane-noisy copy
        from repro.kernels.bsc import bsc_bitplane_copies
        return (lambda k, x, p: bsc_bitplane_copies(k, x, p, interpret=False),
                (sds((SLOTS, RX, 2), u32), sds((SLOTS, RING_TRIALS, W), u32),
                 sds((RX,), jnp.float32)))
    if name == "hamming_topk_banked_k8":
        return (lambda q, p, r: hamming_topk_banked(
            q, p, k=8, bank_rows=r, interpret=False), (ring_q, table, rows))
    if name == "hamming_search_banked":
        return (lambda q, p: hamming_search_banked(q, p, interpret=False),
                (sds((RX, TRIALS, W), u32), banks))
    if name == "hamming_search":
        # the wired baseline's and the classifier's full distance table
        return (lambda q, p: hamming_search(q, p, interpret=False),
                (sds((TRIALS, W), u32), sds((C, W), u32)))
    if name == "sparse_topk_banked":
        return (lambda q, p: sparse_topk_banked(q, p, interpret=False),
                (sds((RX, TRIALS, K_MAX), i32), banks))
    if name == "sparse_search":
        return (lambda q, p: sparse_search(q, p, interpret=False),
                (sds((TRIALS, K_MAX), i32), sds((C, W), u32)))
    if name == "assoc_matmul":
        # the unpacked serve's per-core search (`scaleout._local_search`)
        return (jax.vmap(lambda q, p: assoc_matmul(q, p, bm=8, interpret=False)),
                (sds((RX, TRIALS, D), u8), sds((RX, C_CORE, D), u8)))
    assert name == "majority_bundle"
    return (lambda h: majority_bundle(h, interpret=False),
            (sds((3, TRIALS, D), u8),))


@pytest.mark.parametrize("name", [
    "hamming_topk_banked_k1",
    "hamming_topk_banked_k1_table1",
    "hamming_topk_banked_k8",
    "bsc_bitplane_copies",
    "hamming_search_banked",
    "hamming_search",
    "sparse_topk_banked",
    "sparse_search",
    "assoc_matmul",
    "majority_bundle",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, structs = _kernel_case(
        name, lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip))
    _compile(fn, *structs)


def _serve_structs(mesh, cfg):
    from repro import phy

    rep = NamedSharding(mesh, P())
    model = mesh.axis_sizes[mesh.axis_names.index("model")]
    e_per = -(-cfg.m_tx // model)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    state = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx))
    return sds, state, e_per


@pytest.mark.parametrize("chips,channel,collective", [
    (1, "ideal", "psum_packed"),
    (1, "bsc", "psum_packed"),
    (4, "ideal", "psum"),
    (4, "ideal", "rs_ag"),
])
def test_standalone_serve_compiles_for_v5e(topo, monkeypatch, chips,
                                           channel, collective):
    """The standalone serve at the chip_smoke shapes, on one chip and on the
    (1, 4) mesh of a four-chip host, runs its search as a Mosaic kernel."""
    from repro.core import scaleout
    from repro.kernels import common

    # the described chip is not the process's backend: steer the kernels
    # off interpret mode as they would be on the chip
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=C, dim=D, m_tx=3, n_rx_cores=RX, batch=TRIALS,
        representation="packed", collective=collective, channel=channel,
        noise="bitplane")
    sds, state, e_per = _serve_structs(mesh, cfg)
    compiled = scaleout.make_ota_serve(mesh, cfg).lower(
        sds((C, W), jnp.uint32), sds((TRIALS, chips, e_per, W), jnp.uint32),
        state, sds((2,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ring_step_fits_one_v5e(topo, monkeypatch):
    """The multi-tenant ring step of chip_smoke.py (2 slots x 512 trials,
    8 tenants resident) compiles for one chip: its search is a Mosaic kernel,
    its HBM fits 16 GB (the compiler refuses a program that does not), and
    its ops keep the serve stages' named scopes in their metadata."""
    from repro.core import scaleout
    from repro.kernels import common

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=C, dim=D, m_tx=3, n_rx_cores=RX, batch=RING_TRIALS,
        representation="packed", collective="psum_packed", channel="bsc",
        noise="bitplane")
    sds, state, e_per = _serve_structs(mesh, cfg)
    compiled = scaleout.make_mt_ota_serve(mesh, cfg).lower(
        sds((TENANTS, C, W), jnp.uint32),
        sds((SLOTS, RING_TRIALS, 1, e_per, W), jnp.uint32),
        sds((SLOTS,), jnp.int32), state, sds((SLOTS, 2), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    for scope in ("ota_bundle", "rx_copies", "search", "top1_gather"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


@pytest.mark.parametrize("cell,classes,dim,cores,slots,trials,tenants", [
    ("table1", 6400, 512, 64, 32, 64, 64),
    ("whype", C, D, RX, SLOTS, RING_TRIALS, 64),
])
def test_ring_step_runs_the_rx_kernel_on_v5e(topo, monkeypatch, cell, classes,
                                             dim, cores, slots, trials,
                                             tenants):
    """The benchmark's one-chip ring steps (``table1``: 32 slots x 64 trials,
    64 cores, d=512; ``whype``: 2 slots x 512 trials, 1,024 cores, d=2048;
    64 tenants each) compile with the RX fan-out as the bitplane kernel
    under the ``rx_copies`` scope, and fit one chip. Logs the temporaries."""
    from repro.core import scaleout
    from repro.kernels import common

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=classes, dim=dim, m_tx=3, n_rx_cores=cores, batch=trials,
        representation="packed", collective="psum_packed", channel="bsc",
        noise="bitplane")
    sds, state, e_per = _serve_structs(mesh, cfg)
    w = dim // 32
    compiled = scaleout.make_mt_ota_serve(mesh, cfg).lower(
        sds((tenants, classes, w), jnp.uint32),
        sds((slots, trials, 1, e_per, w), jnp.uint32),
        sds((slots,), jnp.int32), state, sds((slots, 2), jnp.uint32)).compile()
    text = compiled.as_text()
    kernel = re.findall(r"%bsc_bitplane_pallas\S* = .*", text)
    assert len(kernel) == 1 and 'tpu_custom_call' in kernel[0]
    assert "/rx_copies/" in kernel[0]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    print(f"{cell} ring step: {mem.temp_size_in_bytes} bytes of temporaries, "
          f"{mem.argument_size_in_bytes} bytes of arguments")


def test_four_chip_ring_step_fits_each_v5e(topo, monkeypatch):
    """The ``whype4`` ring step (8 slots x 512 trials, 64 tenants resident,
    the 1,024 cores over the (1, 4) mesh of a four-chip host) compiles: its
    search is a Mosaic kernel, each chip's HBM fits 16 GB, and its vote
    all-reduce runs under the ``vote_exchange`` scope nested in
    ``ota_bundle``."""
    from repro.core import scaleout
    from repro.kernels import common

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    chips, slots, tenants = 4, 8, 64
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=C, dim=D, m_tx=3, n_rx_cores=RX, batch=RING_TRIALS,
        representation="packed", collective="psum_packed", channel="bsc",
        noise="bitplane")
    sds, state, e_per = _serve_structs(mesh, cfg)

    def sharded(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    compiled = scaleout.make_mt_ota_serve(mesh, cfg).lower(
        sharded((tenants, C, W), jnp.uint32, P(None, "model", None)),
        sharded((slots, RING_TRIALS, chips, e_per, W), jnp.uint32,
                P(None, None, "model", None, None)),
        sds((slots,), jnp.int32), state, sds((slots, 2), jnp.uint32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    reduce_ops = re.findall(r"= \S+ all-reduce\(.*", text)
    assert reduce_ops and all('ota_bundle/vote_exchange/' in op
                              for op in reduce_ops)
