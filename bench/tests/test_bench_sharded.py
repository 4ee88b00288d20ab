"""On four virtual CPU devices: the cell on the ``sharded`` backend at a
tiny size is correct, and not correct once the exchange between chips is
left out."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp
from bench.tests import tiny
from repro.core import distributed

c = tiny.cell(backend="sharded")
c["traffic"]["clients"] = 128
print(json.dumps(tiny.run(c, seconds=8.0)), flush=True)

def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
    return jnp.stack([x] * 4, axis=axis)   # each shard sees only itself

jax.lax.all_gather = local_only
distributed._bucket_score_fn.cache_clear()
print(json.dumps(tiny.run(c, seconds=8.0)), flush=True)
"""


def test_exchange_left_out_is_not_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, broken = (json.loads(x) for x in out.stdout.strip().splitlines())
    assert sound["correct"] and sound["attempted"] > 0, sound["checks"]
    assert not broken["correct"]
    gap = broken["checks"]["answer_gap"]
    assert gap["value"] > gap["limit"]
