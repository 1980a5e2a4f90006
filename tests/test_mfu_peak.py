"""chip_peak_flops: every v2-v6e spelling resolves from the spec table,
an accelerator the table cannot name is an error (never a guessed
denominator), and the CPU has no peak — so no MFU."""
import pytest

from paddle_tpu.observability import mfu


class FakeDev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


@pytest.mark.parametrize("kind,peak", [
    # both cloud spellings per generation where they differ
    ("TPU v2", 45e12),
    ("TPU v3", 123e12),
    ("TPU v4", 275e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v6 lite", 918e12),
    ("TPU v6e", 918e12),
    # suffixed real-world kinds resolve by prefix
    ("TPU v4 MegaCore", 275e12),
    ("TPU v5p pod slice", 459e12),
    # case drift must not break the lookup
    ("tpu v3", 123e12),
])
def test_peak_table_spellings(kind, peak, monkeypatch):
    monkeypatch.delenv("PD_PEAK_FLOPS", raising=False)
    assert mfu.chip_peak_flops(FakeDev(kind)) == peak


def test_unknown_accelerator_raises(monkeypatch):
    monkeypatch.delenv("PD_PEAK_FLOPS", raising=False)
    with pytest.raises(ValueError, match="Mystery X1"):
        mfu.chip_peak_flops(FakeDev("Mystery X1"))


def test_cpu_has_no_peak_and_no_mfu(monkeypatch):
    monkeypatch.delenv("PD_PEAK_FLOPS", raising=False)
    assert mfu.chip_peak_flops(FakeDev("cpu", platform="cpu")) is None
    # conftest pins the CPU platform: the meter's default peak is None
    meter = mfu.ThroughputMeter(examples_per_step=8, flops_per_step=1e9)
    meter.step(0.01)
    rep = meter.report()
    assert rep["examples_per_sec"] > 0
    assert rep["mfu"] is None and rep["peak_flops_total"] is None


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv("PD_PEAK_FLOPS", "1e15")
    assert mfu.chip_peak_flops(FakeDev("TPU v4")) == 1e15
    assert mfu.chip_peak_flops(FakeDev("Mystery X1")) == 1e15
