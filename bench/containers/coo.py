"""The program's COO container, padded to the configuration's fixed
``capacity`` so that every seed's graph has the same shapes."""

from repro.core import graph as G

from bench.harness import HarnessError


def build(arcs, spec: dict):
  if arcs.num_arcs > spec["capacity"]:
    raise HarnessError(f"{arcs.num_arcs} arcs exceed capacity "
                       f"{spec['capacity']}")
  return G.build_coo(arcs.src, arcs.dst, arcs.w, n=arcs.n,
                     capacity=spec["capacity"]), {}
