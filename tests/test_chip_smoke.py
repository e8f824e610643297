"""The chip smoke test's phases, run at a tiny size on the CPU.

``chip_smoke.py`` is the proof that the device engine runs on a TPU;
only its ``main()`` insists on one.  Each phase function here runs with
the same parity assertions it makes on the chip, so the script's logic
is guarded without a chip.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dict(num_lps=64, msgs_per_lp=4)


@pytest.fixture(scope="module")
def phase_a():
    return chip_smoke.phase_a(**TINY, until=8.0, shared_batches=40)


def test_phase_a_default_path_matches_cpu_reference(phase_a):
    info, shared = phase_a
    assert info["population"] == 256
    assert info["capacity"] >= info["population"]
    assert info["full_events"] >= info["population"]
    assert shared.batches == 40
    assert shared.events == int(np.asarray(shared.state["counts"]).sum())


def test_phase_b_pallas_matches_phase_a(phase_a):
    _, shared = phase_a
    info = chip_smoke.phase_b(shared, **TINY, shared_batches=40)
    assert (info["events"], info["batches"]) == (shared.events, 40)
    # Interpret mode here: no Mosaic kernel in the program.
    assert info["tpu_custom_call"] is False


def test_kernel_differential_at_engine_widths():
    assert chip_smoke.kernel_differential(capacity=512, steps=6) == 6


def test_phase_b_catches_a_divergent_reference(phase_a):
    _, shared = phase_a
    with pytest.raises(AssertionError):
        chip_smoke.phase_b(shared, **TINY, shared_batches=39)


def test_phase_c_streamed_matches_preseeded():
    info = chip_smoke.phase_c(n_requests=600, capacity=128, slots=16,
                              block_size=64)
    assert info["requests"] == 600
    assert info["events"] > info["requests"]


def test_sharded_placement_path_one_shard():
    """The --four-chips comparison's logic, on the one CPU device."""
    info = chip_smoke.four_chips(**TINY, until=6.0, shards=1)
    assert info["shards"] == 1 and len(info["queue_devices"]) == 1
    assert info["events"] > 0


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) == 1
    assert chip_smoke.main(["--four-chips"]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Outside the repo the script cannot run, and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
