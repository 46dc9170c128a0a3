"""`running_hardware`: the chip's table entry by `device_kind` on a TPU, an
error for a TPU kind the table lacks, the analytic v5e target elsewhere."""
from types import SimpleNamespace

import jax
import pytest

from repro.core.hardware import BY_DEVICE_KIND, TPU_V5E, running_hardware


def _fake_devices(monkeypatch, platform, kind):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


def test_cpu_backend_keeps_analytic_target():
    assert jax.devices()[0].platform == "cpu"
    assert running_hardware() is TPU_V5E


@pytest.mark.parametrize("kind", sorted(BY_DEVICE_KIND))
def test_tpu_kind_in_table(monkeypatch, kind):
    _fake_devices(monkeypatch, "tpu", kind)
    assert running_hardware() is BY_DEVICE_KIND[kind]


def test_v5e_reports_v5_lite():
    assert BY_DEVICE_KIND["TPU v5 lite"] is TPU_V5E
    # published peaks (Google Cloud, "TPU v5e")
    assert (TPU_V5E.peak_flops, TPU_V5E.hbm_bw) == (197e12, 819e9)


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        running_hardware()
