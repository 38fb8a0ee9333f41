"""``chip_smoke.py`` on the CPU: its device path runs end to end at a tiny
size (interpreted kernels) and agrees with the numpy reference, and the
script itself refuses to report a result without a TPU."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402
from repro.core import DeviceModel, LSMConfig  # noqa: E402


def test_smoke_path_parity_at_tiny_size():
    scale = 1 << 16
    cfg = LSMConfig.vlsm_default(scale=scale)
    dm = DeviceModel.scaled(scale / (64 << 20))
    stream = chip_smoke.make_stream(7, n_load=4_000, n_run=1_000)
    probe = np.concatenate([stream.keys[:300], stream.keys[:300] + 1])
    dev = chip_smoke.run_tier("pallas", cfg, dm, stream, probe)
    ref = chip_smoke.run_tier("numpy", cfg, dm, stream, probe)
    assert sum(len(lv) for lv in dev.engine.trees[0].levels[1:]) > 0
    assert all(chip_smoke.kernel_shapes().values())    # every kernel ran
    parity = chip_smoke.compare(dev, ref)
    assert parity["max_abs_d_departure_s"] <= parity["bound_s"]
    assert set(chip_smoke.tails(dev, stream.n_load)) == {
        "p99_get_ms", "p99.9_get_ms", "p99_put_ms", "p99.9_put_ms"}


def test_smoke_refuses_without_a_tpu(capsys):
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
