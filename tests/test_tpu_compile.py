"""Compile the main path for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology it
only describes, and refuses what the chip would refuse (tiles that do not
align, kernels that overrun VMEM, programs that do not fit), which the
interpret-mode kernel tests cannot see.  Shapes are the chip smoke's: the
MSD-like width d = 90 (padded to 128 by the kernels), eps = 0.1 so the
sketch keeps l = ceil(4 / eps) = 40 rows, 1024 queries, 32 packed tenants.
Each test asserts that the compiled program holds the Pallas kernel
(``tpu_custom_call``) or, across four chips, the site collective
(``all-gather``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, so every
pytest worker must collect these tests and only the one that runs them
loads it.  The persistent compilation cache is off around them, since an
entry compiled for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed as dist
from repro.kernels import fd_ops, ops

D, EPS, L, N, T = 90, 0.1, 40, 1024, 32
BATCH = 4000  # rows per site per super-step, as the chip smoke ingests
# P2's per-site state leaves (sharded over the sites axis); the rest is
# replicated on every site.
P2_PER_SITE = ("site_fd", "f_j")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_quadform_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda b, x: ops.quadform(b, x, interpret=False),
        _spec((L, D), one_chip), _spec((N, D), one_chip),
    )
    assert "tpu_custom_call" in text


def test_quadform_packed_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda b, x: ops.quadform_packed(b, x, interpret=False),
        _spec((T, L, D), one_chip), _spec((T, N, D), one_chip),
    )
    assert "tpu_custom_call" in text


def test_levscore_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda m, x: ops.levscore(m, x, interpret=False, path="pallas"),
        _spec((D, D), one_chip), _spec((N, D), one_chip),
    )
    assert "tpu_custom_call" in text


def test_fd_spectra_gram_and_projection_compile_for_v5e(one_chip):
    text = _compiled_text(
        lambda b: ops.fd_spectra(b, interpret=False, path="pallas"),
        _spec((T, L, D), one_chip),
    )
    assert text.count("tpu_custom_call") >= 2  # batched Gram + batched projection


def _p2_superstep_text(topo, n_chips: int, *, use_pallas: bool) -> str:
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("sites",))
    cfg = dist.ProtocolConfig(eps=EPS, m=n_chips, d=D, axis="sites", use_pallas=use_pallas)
    state0, step = dist.make_protocol_runner("P2", cfg, mesh)

    def leaf_specs(name, leaf):
        spec = P("sites") if name in P2_PER_SITE else P()
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=NamedSharding(mesh, spec)),
            leaf,
        )

    state = type(state0)(**{n: leaf_specs(n, getattr(state0, n)) for n in state0._fields})
    rows = _spec((n_chips * BATCH, D), NamedSharding(mesh, P("sites", None)))
    return step.lower(state, rows).compile().as_text()


def test_p2_superstep_with_pallas_shrink_compiles_for_one_v5e(topo, monkeypatch):
    # The FD shrink picks its kernels from the default backend, which is
    # the CPU here: steer it to the chip's branch, and drop traces taken
    # on the CPU branch so they cannot be reused.
    monkeypatch.setattr(fd_ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        text = _p2_superstep_text(topo, 1, use_pallas=True)
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text


def test_p2_superstep_gathers_sites_across_four_v5e(topo):
    text = _p2_superstep_text(topo, 4, use_pallas=False)
    assert "all-gather" in text
