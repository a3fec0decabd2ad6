"""Ahead-of-time compiles of the device search for a described TPU v5e.

The TPU compiler is installed without a chip, so the kernels and the fused
window program of ``algo="beam_jax"`` are compiled here for a v5e that is
described, not attached.  That catches what interpret mode cannot: refused
ops, tiling, and scoped-VMEM overruns.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture (never while a
module is imported), and every compile is made in this process with the
persistent compilation cache off: a described-device compile written to the
cache cannot be read back without a chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SearchConfig, get_scenario, make_mcm, schedule
from repro.core import device_search as ds
from repro.core.engine import DeviceBeamEngine
from repro.kernels.scar_eval import scar_eval
from repro.kernels.scar_search import conflict_counts


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_scar_search_compiles_at_16x16_width(one_chip):
    """The disjointness screen at beam 128 x 8192 candidates x 8 words."""
    beam = _shape(one_chip, (128, 8), jnp.uint32)
    cand = _shape(one_chip, (8192, 8), jnp.uint32)
    compiled = conflict_counts.lower(beam, cand, use_kernel=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scar_eval_dense_form_compiles_at_16x16_window(one_chip):
    """The scoring kernel at a 16x16 window's largest batch: 32768
    candidates of a 56-layer model slice, 2 classes, 6 segments."""
    B, L, C, S = 32768, 56, 2, 6
    f32, i32 = jnp.float32, jnp.int32
    args = (_shape(one_chip, (L, C), f32), _shape(one_chip, (L, C), f32),
            _shape(one_chip, (L, B), i32), _shape(one_chip, (L, B), i32),
            _shape(one_chip, (S, B), f32), _shape(one_chip, (S, B), f32),
            _shape(one_chip, (S, B), f32), _shape(one_chip, (1, B), f32))
    compiled = jax.jit(scar_eval).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_program_compiles_with_kernels(one_chip, monkeypatch):
    """One window of a small ``beam_jax`` schedule, captured as the engine
    hands it to ``fused_program`` with the Pallas kernels selected, then
    compiled from its shapes."""
    captured = []

    class _Captured(Exception):
        pass

    def capture(inputs, **statics):
        captured.append((inputs, statics))
        raise _Captured

    compile_fused = ds.fused_program
    monkeypatch.setattr(DeviceBeamEngine, "uses_kernels", lambda self: True)
    monkeypatch.setattr(ds, "fused_program", capture)
    with pytest.raises(_Captured):
        schedule(get_scenario("xr7_ar_gaming"),
                 make_mcm("het_sides", n_pe=256), SearchConfig(algo="beam_jax"))
    inputs, statics = captured[0]
    assert statics["use_kernel"] and not statics["interpret"]
    shapes = jax.tree.map(
        lambda a: _shape(one_chip, jnp.shape(a), jnp.result_type(a)), inputs)
    compiled = compile_fused.lower(shapes, **statics).compile()
    assert "tpu_custom_call" in compiled.as_text()
