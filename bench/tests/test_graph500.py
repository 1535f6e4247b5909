"""The device generator at SCALE 10: clean, symmetric, seeded, and drawn
with the Graph500 quadrant probabilities."""

import jax
import numpy as np
import pytest

from bench.gen import graph500

SCALE = 10
ABC = (0.57, 0.19, 0.19)


@pytest.fixture(scope="module")
def arcs():
  return graph500.generate(12345, scale=SCALE)


def test_arcs_are_symmetric_with_equal_weights(arcs):
  fwd = dict(zip(zip(arcs.src.tolist(), arcs.dst.tolist()), arcs.w.tolist()))
  back = dict(zip(zip(arcs.dst.tolist(), arcs.src.tolist()), arcs.w.tolist()))
  assert fwd == back


def test_no_loops_no_duplicates(arcs):
  assert not np.any(arcs.src == arcs.dst)
  key = arcs.src.astype(np.int64) * arcs.n + arcs.dst
  assert np.unique(key).size == key.size


def test_ranges(arcs):
  assert arcs.n == 1 << SCALE
  assert arcs.src.dtype == np.int32 and arcs.w.dtype == np.float32
  assert arcs.src.min() >= 0 and arcs.src.max() < arcs.n
  assert np.all((arcs.w >= 0) & (arcs.w < 1))
  # Duplicates merge, yet most of the 16 * 2^SCALE tuples stay edges.
  assert 0.5 * (16 << SCALE) < arcs.num_arcs / 2 <= 16 << SCALE


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3])
def test_same_seed_same_graph(arcs, seed):
  a = graph500.generate(seed, scale=SCALE)
  b = graph500.generate(seed, scale=SCALE)
  for x, y in ((a.src, b.src), (a.dst, b.dst), (a.w, b.w)):
    np.testing.assert_array_equal(x, y)
  assert not (a.num_arcs == arcs.num_arcs
              and np.array_equal(a.src, arcs.src))


def test_level_bits_follow_abcd():
  """Each level's (row bit, col bit) lands in A, B, C, D at their rates,
  within five standard errors of the sample."""
  row, col = graph500.kronecker_tuples(graph500.seed_key(99), scale=SCALE,
                                       edgefactor=16, abc=ABC)
  row, col = np.asarray(row), np.asarray(col)
  m = row.size
  probs = np.array([ABC[0], ABC[1], ABC[2], 1 - sum(ABC)])
  tol = 5 * np.sqrt(probs * (1 - probs) / m)
  for level in range(SCALE):
    r = (row >> level) & 1
    c = (col >> level) & 1
    freq = np.bincount(2 * r + c, minlength=4) / m
    np.testing.assert_array_less(np.abs(freq - probs), tol)


def test_seed_must_fit_64_bits():
  with pytest.raises(ValueError):
    graph500.seed_key(-1)
  assert jax.numpy.asarray(graph500.seed_key(2**64 - 1)).shape == (2,)


def _unlabelled(a):
  """The weighted arcs under the labels they had before the permutation."""
  inv = np.argsort(a.perm)
  return sorted(zip(inv[a.src].tolist(), inv[a.dst].tolist(), a.w.tolist()))


def test_graph_seed_fixes_the_graph_under_new_labels():
  """A configuration's graph seed gives every run seed the same weighted
  graph, and the SSSP cell the same roots, under the run's own labels."""
  import types
  from bench import harness
  a = graph500.generate(5, scale=SCALE, graph_seed=1)
  b = graph500.generate(2**33 + 5, scale=SCALE, graph_seed=1)
  assert a.graph_seed == b.graph_seed == 1
  assert not np.array_equal(a.perm, b.perm)
  assert _unlabelled(a) == _unlabelled(b)
  batch = harness.load_part("programs", "sssp").Batch
  roots = []
  for arcs, seed in ((a, 5), (b, 2**33 + 5)):
    ctx = types.SimpleNamespace(arcs=arcs, plan=None, seed=seed,
                                traffic={"roots": 3})
    inv = np.argsort(arcs.perm)
    roots.append(sorted(int(inv[k]) for k in batch(ctx).keys))
  assert roots[0] == roots[1]
