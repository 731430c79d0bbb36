"""The four-chip cell large.batch.x4 driven through a whole run on the CPU,
in a subprocess with four forced host devices: a 30x10 mesh, the pure-XLA
backends, 14-iteration requests, a 3-second window. Its configuration
keeps cronet-large's limits; here the sound run comes out correct with
nothing compiled in the window and every shard serving, and the control
(the reference in bf16 in the program's place) and the planted faults
come out not correct."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

SCRIPT = r"""
import json, re
from bench import run
lines = []
res = run.run_cell(
    "large.batch.x4", 2**32 + 91, 3.0, False,
    overrides={"nelx": 30, "nely": 10, "backend": "oracle",
               "fea_backend": "reference"},
    mix_overrides={"n_iter": 14}, allow_cpu=True, variants=True,
    log=lines.append)
from repro.obs.metrics import default_registry
steps = default_registry().counter("topo_steps_total")
res["shard_steps"] = {dict(k)["shard"]: steps.value(**dict(k))
                      for k in steps.labelsets()}
res["in_window"] = [int(m) for l in lines for m in re.findall(
    r"programs compiled or loaded in the window: (\d+)", l)]
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax"))
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src")])
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2500:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_sound_run_is_correct_on_four_shards(result):
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["device"]["count"] == 4
    assert sorted(result["shard_steps"]) == ["0", "1", "2", "3"]
    assert all(v > 0 for v in result["shard_steps"].values())
    assert result["in_window"] == [0]


def test_control_and_faults_are_not_correct(result):
    ctl = result["variants"]["control_bf16"]
    assert not ctl["correct"]
    for name, c in ctl["checks"].items():
        assert c["value"] > c["limit"], (name, c)
    for fault in ("filter_oc_bf16", "altered_answer", "unchanged_step"):
        assert not result["variants"][fault]["correct"], fault
