"""``correct`` on CPU at a small size: a sound run passes, and each fault a
training cell can have, planted underneath the timed path, makes a whole
run (set-up, window, check) report ``correct`` false.  So does the control,
the fp8 reference put in the program's place.  The chip's own look for an
accelerator is skipped: the harness is driven directly."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

import compare
import harness
from small import small_cell


def _run(pipe):
    c = small_cell(pipe)
    return harness.run_cell(c, 2**33 + 7, 0.5, False, jax.devices(), time.perf_counter(), log=lambda s: None)


@pytest.mark.parametrize("pipe", [1, 4])
def test_sound_run_is_correct(pipe):
    out = _run(pipe)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("pipe", [1, 4])
def test_state_left_unchanged_is_caught(pipe, monkeypatch):
    from repro.optim import adamw

    monkeypatch.setattr(adamw, "step", lambda params, state, *a, **k: (params, state))
    monkeypatch.setattr(adamw, "rollback", lambda params, state, *a, **k: (params, state))
    assert not _run(pipe)["correct"]


@pytest.mark.parametrize("pipe", [1, 4])
def test_half_batch_is_caught(pipe, monkeypatch):
    from repro.launch import train

    real = train.side_from_batch

    def half(batch, spec, *a, **k):
        side = real(batch, spec, *a, **k)
        m = side["tokens"].shape[0] // 2
        for key in ("tokens", "labels"):
            side[key] = jnp.concatenate([side[key][:m], side[key][:m]])
        return side

    monkeypatch.setattr(train, "side_from_batch", half)
    assert not _run(pipe)["correct"]


def test_exchange_left_out_is_caught(monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, *a, **k: jnp.zeros_like(x))
    assert not _run(4)["correct"]


@pytest.mark.parametrize("pipe", [1, 4])
def test_fp8_control_is_not_correct(pipe):
    b = harness.Bench(small_cell(pipe), jax.devices(), 2**33 + 7)
    ref = b.reference()
    ctrl = b.reference(quant="fp8")
    ok, checks = compare.judge(compare.numbers(ctrl, ref), b.cell.limits)
    assert not ok, checks


def test_traced_run_reads_its_per_layer_metrics(monkeypatch):
    """The --trace 1 path end to end on the CPU: the CPU has no device
    planes, so only the host-span and host-clock readers find something;
    a scoped reader (``f_ms``) takes its own profile and reads nothing."""
    import peaks

    monkeypatch.setattr(peaks, "peak", lambda kind: peaks.PEAKS["TPU v5 lite"])
    c = small_cell(1)
    per_layer = [
        {"name": "host_data_ms", "unit": "ms"},
        {"name": "mfu", "unit": "%"},
        {"name": "device_idle_share", "unit": "%"},
        {"name": "f_ms", "unit": "ms"},
    ]
    c = dataclasses.replace(c, per_layer=per_layer)
    out = harness.run_cell(c, 3, 0.5, True, jax.devices(), time.perf_counter(), log=lambda s: None)
    assert out["correct"]
    assert set(out["metrics"]) == {"host_data_ms", "mfu"}
    assert out["metrics"]["host_data_ms"]["value"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0
