"""chip_smoke.py off the chip: importing it touches no device, its
``main()`` refuses to run without a TPU, and its phases pass at a
quarter of the published widths on the CPU (Pallas in interpret mode).
"""

import json
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_import_touches_no_device():
    code = (
        "import chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


def test_main_exits_nonzero_off_the_chip(capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and out[0].startswith(f"jax {jax.__version__}: platform=cpu")
    for line in out:
        assert '"ok"' not in line
    # refused before the compile cache is turned on
    assert jax.config.jax_compilation_cache_dir == cache_dir


def test_kernel_phase_small_widths():
    assert chip_smoke.kernel_phase(scale=0.25, batch_sizes=(2,)) > 0


def test_main_path_phase_small_widths(capsys):
    result = chip_smoke.main_path_phase(
        scale=0.25, batch_sizes=(1, 4), n_requests=24
    )
    assert result["served"] == 24
    assert set(result["phase_s"]) == {"profile", "map", "fuse", "serve"}
    out = capsys.readouterr().out
    assert "all bit-exact vs forward_packed" in out
    assert "-> output on" in out
    json.dumps(result)
