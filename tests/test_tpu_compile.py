"""Compile the served path for a described v5e chip (no chip needed).

The TPU compiler describes a ``v5e:2x2`` topology without hardware;
each test compiles for one of its chips at chip_smoke.py's widths
(d 768, k 100, list_pad 256, the scheduler's wave and chunk, a
4096-slot delta buffer) and asserts that the Mosaic kernel is in the
program (``tpu_custom_call``).  Nothing runs, so results and times are
out of scope: this only guards what the chip's compiler accepts.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import serving
from repro.core.ivf import DeltaView, IVFIndex
from repro.kernels import ops

D, K, LIST_PAD, CAP = 768, 100, 256, 4096
WAVE, CHUNK, N_PROBE, N_CLUSTERS = 64, 8, 80, 16384
ROWS = 3 << 20           # about the cluster-major rows of 2^21 docs


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


@pytest.fixture
def mosaic(monkeypatch):
    """This process's backend is the CPU, so the ops wrappers would pick
    interpret mode: steer them to Mosaic, with no compile cache."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn, **kwargs).lower(*args).compile().as_text()


@pytest.fixture
def spec(one_chip):
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)


@pytest.mark.parametrize("with_delta", [False, True],
                         ids=["static", "delta"])
def test_fused_kernel_compiles_for_v5e(spec, mosaic, with_delta):
    i32 = jnp.int32
    args = [spec((WAVE, D)), spec((ROWS, D)), spec((ROWS,), i32),
            spec((WAVE, CHUNK), i32), spec((WAVE, CHUNK), i32),
            spec((WAVE, K)), spec((WAVE, K), i32)]
    if with_delta:
        args += [spec((CAP, D)), spec((CAP,), i32), spec((CAP,), i32),
                 spec((WAVE, CHUNK), i32)]
    text = _compiled_text(
        lambda *a: ops.ivf_scan_merge(*a, k=K, list_pad=LIST_PAD,
                                      chunk=CHUNK), *args)
    assert "tpu_custom_call" in text


def test_advance_step_compiles_for_v5e(spec, mosaic):
    """The jitted wave step as the live scheduler calls it: fused kernel
    with the in-kernel delta stream and the tombstone scrub."""
    i32 = jnp.int32
    index = IVFIndex(spec((N_CLUSTERS, D)), spec((ROWS, D)),
                     spec((ROWS,), i32), spec((N_CLUSTERS,), i32),
                     spec((N_CLUSTERS,), i32), LIST_PAD)
    state = serving.LaneState(
        spec((WAVE, D)), spec((WAVE, N_PROBE), i32), spec((WAVE,), i32),
        spec((WAVE, K)), spec((WAVE, K), i32), spec((WAVE,), i32),
        spec((WAVE,), jnp.bool_), spec((WAVE,), i32))
    dview = DeltaView(spec((CAP, D)), spec((CAP,), i32), spec((CAP,), i32))
    lane = spec((WAVE,), i32)
    text = serving._advance.lower(
        index, state, dview, spec((1 << 21,), jnp.bool_), lane_delta=lane,
        lane_cap=lane, chunk=CHUNK, k=K, n_probe=N_PROBE,
        phi=95.0).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["ivf_scan", "topk_merge", "delta_scan"])
def test_unfused_kernels_compile_for_v5e(spec, mosaic, kernel):
    i32 = jnp.int32
    if kernel == "ivf_scan":
        text = _compiled_text(
            lambda q, d, o, s: ops.ivf_scan(q, d, o, s, list_pad=LIST_PAD),
            spec((WAVE, D)), spec((ROWS, D)), spec((WAVE,), i32),
            spec((WAVE,), i32))
    elif kernel == "topk_merge":
        text = _compiled_text(
            lambda s, i, ns, ni: ops.topk_merge(s, i, ns, ni, K),
            spec((WAVE, K)), spec((WAVE, K), i32), spec((WAVE, LIST_PAD)),
            spec((WAVE, LIST_PAD), i32))
    else:
        text = _compiled_text(ops.delta_scan, spec((WAVE, D)),
                              spec((CAP, D)))
    assert "tpu_custom_call" in text
