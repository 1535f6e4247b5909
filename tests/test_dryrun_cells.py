"""Dry-run machinery integration test (subprocess: needs 512 fake devices).

Compiles two representative cells on the production meshes and checks the
recorded metrics are sane; also checks the skip rule.
"""

import json
import os
import subprocess
import sys

import pytest


_CHILD = r"""
import json
from repro.launch.dryrun import run_cell, build_cell, SkipCell

out = {}
rec = run_cell("granite-3-2b", "decode_32k", False, cache_layout="seq")
out["decode"] = dict(flops=rec["flops"], coll=rec["collective_bytes"],
                     devices=rec["devices"])
rec2 = run_cell("granite-3-2b", "train_4k", True)  # multi-pod
out["train_mp"] = dict(flops=rec2["flops"], devices=rec2["devices"])
try:
    build_cell("granite-3-2b", "long_500k", False)
    out["skip"] = False
except SkipCell:
    out["skip"] = True
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_cells_compile_and_record():
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")])
  res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
  assert res.returncode == 0, res.stderr[-3000:]
  line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][-1]
  out = json.loads(line[len("RESULT:"):])
  assert out["skip"] is True                      # full-attn long_500k
  assert out["decode"]["devices"] == 256
  assert out["train_mp"]["devices"] == 512        # multi-pod mesh
  assert out["decode"]["flops"] > 0
  # seq-layout decode must not move gigabytes per token.
  assert out["decode"]["coll"] < 1e9
  assert out["train_mp"]["flops"] > 1e13
