"""``chip_smoke.py`` at a tiny size on the CPU.

The script refuses to run without a TPU, so these tests call its phases
directly with the reduced config: the one-chip phases in this process, the
four-chip phases in a child with four virtual CPU devices
(``_chip_smoke_check.py``). They keep the script runnable between chip
runs; they say nothing about the chip.
"""
import os
import sys

import pytest

from repro.ckpt import compile_cache
from repro.configs.base import GNNConfig
from test_distributed import run_script

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    ladder=(256, 512), requests=((1, 256), (2, 256), (3, 512)),
    rollout_steps=(3, 6), rollout_slots=2, train_levels=(128, 256, 512),
    train_samples=2, train_steps=2, four_bucket=256, four_partitions=4)


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


def test_failed_checks_fail_the_run_after_every_phase(capsys):
    ran = []

    def boom():
        raise ValueError("crash")

    with pytest.raises(chip_smoke.SmokeFailure, match="2 checks failed"):
        chip_smoke.run_phases([
            ("a", lambda: chip_smoke.fail("a bound")), ("b", boom),
            ("c", lambda: ran.append("c"))])
    assert ran == ["c"]
    out = capsys.readouterr().out
    assert "FAIL: a bound" in out and "phase b raised ValueError" in out


def test_one_chip_phases(tmp_path, cache_dir):
    compile_cache.enable(cache_dir)    # the script enables it in main()
    workdir = tmp_path / "artifacts"
    chip_smoke.run_one_chip(GNNConfig().reduced(), TINY, str(workdir))
    assert not os.listdir(workdir)     # the deploy artifact was removed


def test_four_chip_phases_on_virtual_devices():
    out = run_script("_chip_smoke_check.py")
    assert "ALL_OK" in out
