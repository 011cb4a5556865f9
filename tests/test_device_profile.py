"""Device-profile parsing: device-side timing from perfetto traces (bench
numbers must separate the device's own time from host noise)."""

import jax
import jax.numpy as jnp
import pytest

from pilottai_tpu.utils.device_profile import (
    DeviceWindow,
    parse_trace_dir,
    profile_device_window,
)


def test_profile_window_measures_compute(tmp_path):
    @jax.jit
    def f(x):
        for _ in range(4):
            x = x @ x
        return x

    x = jnp.ones((256, 256))
    f(x).block_until_ready()  # compile outside the window

    def run():
        y = x
        for _ in range(8):
            y = f(y)
        y.block_until_ready()

    out = profile_device_window(run, trace_dir=str(tmp_path))
    assert out["device_busy_s"] > 0
    assert out["n_events"] > 0
    assert 0 < out["busy_frac"] <= 1.0
    assert out["window_wall_s"] >= out["device_busy_s"] * out["busy_frac"] * 0.1


def test_parse_empty_dir_returns_zeros(tmp_path):
    out = parse_trace_dir(str(tmp_path))
    assert out["device_busy_s"] == 0.0
    assert out["n_events"] == 0


def test_device_window_start_stop(tmp_path):
    win = DeviceWindow(trace_dir=str(tmp_path)).start()
    jnp.ones((64, 64)).sum().block_until_ready()
    out = win.stop()
    assert out["window_wall_s"] > 0
