"""The four-replica document-QA cell at a tiny size on four simulated CPU
devices (``--xla_force_host_platform_device_count=4`` in a subprocess, so
the flag holds before JAX starts): ``dream-7b-l7`` (GQA with q/k/v bias)
under ``docqa-backlog``, four lanes behind the ``least_loaded`` router,
through ``run_cell``.  The run is ``correct`` with the cell's own limits,
each lane's state is committed to a device of its own, and the router put
work on every lane."""
import json
import os
import subprocess
import sys

from bench import run as runmod

ROOT = runmod.ROOT

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses, json, sys, time
ROOT = sys.argv[1]
sys.path[:0] = [ROOT + "/tests/bench", ROOT + "/src", ROOT]
import jax
from bench import run as runmod, spec
from conftest import _set_flag, tiny_cell

assert len(jax.devices()) == 4, jax.devices()
name = "dream-docqa-replicas4"
cell = spec.load_cell(name)
tiny = tiny_cell(cell.config["name"], cell.traffic_name)
argv = tiny.config["serve_argv"]
_set_flag(argv, "--shards", "4")
_set_flag(argv, "--batch", "16")
tiny = dataclasses.replace(tiny, name=name, chips=cell.chips,
                           limits=cell.limits, end_to_end=cell.end_to_end)
seen = {}

def lanes_on_devices(served):
    seen["devices"] = [str(d) for d in served.sched.devices]
    seen["lanes"] = [[str(d) for d in lane.state.tokens.devices()]
                     for lane in served.lanes]
    seen["params"] = [sorted(str(d) for d in
                             jax.tree.leaves(lane.params)[0].devices())
                      for lane in served.lanes]
    seen["sched"] = served.sched

res = runmod.run_cell(tiny, 2**34 + 77, 4.0, False, t_start=time.monotonic(),
                      cache=False, patch=lanes_on_devices)
seen["placed"] = list(seen.pop("sched").placed)
print("RESULT " + json.dumps({"res": res, "seen": seen,
                              "e2e": [m.name for m in cell.end_to_end]}))
"""


def test_four_replicas_on_four_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _SCRIPT, str(ROOT)],
                       capture_output=True, text=True, env=env, timeout=900,
                       cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-4000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    res, seen = out["res"], out["seen"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == set(out["e2e"])
    devices = seen["devices"]
    assert len(set(devices)) == 4
    assert seen["lanes"] == [[d] for d in devices]
    assert seen["params"] == [[d] for d in devices]
    assert len(seen["placed"]) == 4 and min(seen["placed"]) > 0
