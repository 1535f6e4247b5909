"""The comparison that decides ``correct``: numbers and their limits.

An algorithm's reference, control and numbers live in
``bench/reference/<algorithm>.py``.  Each number is the worst reading over
every answer compared.  A number that is not finite fails.  Limits live in
the traffic mix's file, under ``limits``; every number a comparison yields
must have one there.
"""

from __future__ import annotations

import sys
from typing import Dict, Hashable, List, Sequence, Tuple

import numpy as np

Numbers = Dict[str, float]
Answers = Sequence[Tuple[Hashable, np.ndarray]]   # (key, vertex values)


def keys_of(answers: Answers) -> List[Hashable]:
  """The distinct keys (roots, sources; None) of ``answers``, in order."""
  return list(dict.fromkeys(k for k, _ in answers))


def compare(algorithm: str, answers: Answers, arcs, config: dict,
            missing: int = 0, answers_of: str = "reference") -> Numbers:
  """The numbers for ``answers`` against the reference on ``arcs``.

  ``missing`` answers (never given, or an error) fail as the algorithm's
  ``numbers`` says; no answer at all fails every number.
  ``answers_of="control"`` compares the control's answers at the same keys
  in place of ``answers``.
  """
  from bench.harness import load_part
  mod = load_part("reference", algorithm)
  if not answers:
    return {k: float("inf") for k in mod.NUMBERS}
  keys = keys_of(answers)
  want = dict(zip(keys, mod.reference(arcs, config, keys)))
  if answers_of == "control":
    answers = list(zip(keys, mod.control(arcs, config, keys)))
    missing = 0
  return mod.numbers([(a, want[k]) for k, a in answers], arcs.n, missing)


def judge(numbers: Numbers, limits: Dict[str, float]) -> Tuple[bool, dict]:
  """(correct, {name: {"value": v, "limit": l}}); every number needs a limit."""
  missing = sorted(set(numbers) - set(limits))
  if missing:
    raise KeyError(f"no limit for {missing}")
  table = {k: {"value": float(v), "limit": float(limits[k])}
           for k, v in sorted(numbers.items())}
  ok = all(np.isfinite(t["value"]) and t["value"] <= t["limit"]
           for t in table.values())
  return ok, table


def print_table(table: dict, file=sys.stderr) -> None:
  """The numbers compared, each beside its limit, as the last lines."""
  for name, t in table.items():
    print(f"check {name} {t['value']!r} limit {t['limit']!r}", file=file,
          flush=True)
