"""``chip_smoke.py`` off the chip: its phases at a tiny size, and its refusal
to report a result where there is no TPU or no repository beside it."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# A few hundred documents over the TS corpora's three fields, shrunk widths.
TINY = {
    "n_docs": 600, "k_clusters": 12, "field_dims": (32, 32, 64),
    "vocab_sizes": (400, 600, 1500), "n_topics": 20,
    "topic_mix_alpha": 1.0, "noise_terms": (4, 2, 24),
}


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_on_a_tiny_corpus():
    """The kernels' paths (interpret mode): fpf_fused build, fused search
    against reference and brute force, the exact tier, the async tier."""
    smoke = _load_smoke()
    lines: list[str] = []
    out = smoke.run_phases(
        TINY, method="fpf_fused", backend="fused", n_requests=8,
        log=lines.append,
    )
    assert out["clusterer"] == "fpf_fused" and out["backend"] == "fused"
    assert out["exact_mismatches"] == 0
    assert 0.0 < out["cr"] <= 1.0
    assert [ln[:3] for ln in lines] == ["[a]", "[b]", "[c]", "[d]", "[e]"]


def test_sharded_phases_pass_on_four_cpu_devices():
    """The ``--chips 4`` path at a tiny size: ``auto`` resolves to the
    fused backend over a forced four-device CPU mesh (a fresh process, so
    the device count can be set), its pack built one slice per device, and
    the async tier answers as the synchronous path does."""
    code = (
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(ROOT / 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        f"out = m.run_phases({dict(TINY, n_docs=601)!r}, n_requests=8)\n"
        "print(json.dumps({k: out[k] for k in\n"
        "      ('backend', 'shards', 'exact_mismatches', 'pack_share_bytes',\n"
        "       'async_s')}))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["backend"] == "fused" and out["shards"] == 4
    assert out["exact_mismatches"] == 0
    assert out["pack_share_bytes"] > 0 and out["async_s"] > 0


def test_compare_tells_near_ties_from_wrong_answers():
    smoke = _load_smoke()
    s = [[0.9, 0.5, 0.5]]
    assert smoke._compare([[1, 2, 3]], s, [[1, 2, 3]], s) == (0, 0)
    # two documents tied at the k-th score: either may be returned
    assert smoke._compare([[1, 2, 3]], s, [[1, 2, 4]], s) == (1, 0)
    assert smoke._compare([[1, 3, 2]], s, [[1, 2, 3]], s) == (1, 0)
    # a document the other side ranks well above the cut is a wrong answer
    assert smoke._compare([[4, 2, 3]], s, [[1, 2, 3]], s) == (1, 1)


def _run(script: Path, cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_exits_nonzero_off_the_chip():
    r = _run(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_exits_nonzero_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
