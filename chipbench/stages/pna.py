"""Stage builders of the FlowGNN PNA message-passing layer, for the
plain reference (``chipbench/bench/reference.py``).

Written from the design's description, not from the program: FlowGNN
(Sarkar et al., HPCA 2023) streams a PNA layer (Corso et al., NeurIPS
2020) through a node loader, an edge loader, a scatter and one
aggregator per PNA aggregation, a combine and a store, under FlowGNN's
gather contract: edges arrive sorted by destination, so each node's
messages arrive together and each aggregator reads a node's in-degree,
then that many messages.  The scatter needs edge (u, v)'s source
feature u and pulls the node loader's feature stream forward until it
holds u, so how far the loader runs ahead follows the graph.

The graph is the layer's input data, not code under test: the seeded
multigraph of the FIFOAdvisor case study's design (arXiv:2510.20981,
section IV-D).  ANSI C's ``rand`` recurrence (x <- 1103515245 x + 12345
mod 2^31), started from Knuth's multiplicative hash of the seed, draws
each edge's source, then its destination: when that draw is a multiple
of 4 (a quarter of edges), from a hub set of the first
``max(n_nodes // 16, 1)`` nodes.  Edges are sorted stably by
destination.

A task's record names its streams and delays, and ``graph`` (``n_nodes``,
``n_edges``, ``seed``) where its op counts follow the graph.  The edge
loader and the store stream one item a cycle and are the reference's
built-in ``producer`` and ``sink``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from bench.reference import Ops

_A, _C, _M = 1103515245, 12345, 2 ** 31


def graph(n_nodes: int, n_edges: int, seed: int
          ) -> Tuple[List[Tuple[int, int]], List[int]]:
    """``(edges, in_degrees)``: ``n_edges`` (source, destination) pairs,
    sorted by destination."""
    x = (seed * 2654435761 + _C) % _M
    hubs = max(n_nodes // 16, 1)
    edges = []
    for _ in range(n_edges):
        x = (_A * x + _C) % _M
        u = x % n_nodes
        x = (_A * x + _C) % _M
        v = (x // 7) % (hubs if x % 4 == 0 else n_nodes)
        edges.append((u, v))
    edges.sort(key=lambda e: e[1])
    deg = [0] * n_nodes
    for _, v in edges:
        deg[v] += 1
    return edges, deg


def _graph(rec: Dict):
    g = rec["graph"]
    return graph(g["n_nodes"], g["n_edges"], g["seed"])


def node_loader(fifos, r):
    """Per node, in node order: its self-feature to the combine's skip
    stream, its feature to the scatter, its in-degree to each
    aggregator."""
    o = Ops()
    skip, feat = fifos[r["skip"]], fifos[r["feat"]]
    degs = [fifos[name] for name in r["deg"]]
    for v, _ in enumerate(_graph(r)[1]):
        o.delay(r["ii"])
        o.write(skip[v % len(skip)])
        o.write(feat[v % len(feat)])
        for d in degs:
            o.write(d[v % len(d)])
    return o.done()


def scatter(fifos, r):
    """Per edge (u, v): read it, read features until feature u has
    arrived, then send a message to each aggregator's lane
    ``v % lanes``."""
    o = Ops()
    edges_q, feat = fifos[r["edges"]], fifos[r["feat"]]
    msgs = [fifos[name] for name in r["msg"]]
    pulled = 0
    for i, (u, v) in enumerate(_graph(r)[0]):
        o.delay(r["ii"])
        o.read(edges_q[i % len(edges_q)])
        while pulled <= u:
            o.read(feat[pulled % len(feat)])
            pulled += 1
        o.delay(r["send_delay"])
        for lanes in msgs:
            o.write(lanes[v % len(lanes)])
    return o.done()


def aggregate(fifos, r):
    """Per node v: read its in-degree, then that many messages from lane
    ``v % lanes`` at ``per_msg`` cycles each, then after ``epilogue``
    cycles write the aggregate."""
    o = Ops()
    deg_q, lanes, out = fifos[r["deg"]], fifos[r["msg"]], fifos[r["out"]]
    for v, dv in enumerate(_graph(r)[1]):
        o.delay(r["ii"])
        o.read(deg_q[v % len(deg_q)])
        for _ in range(dv):
            o.read(lanes[v % len(lanes)])
            o.delay(r["per_msg"])
        o.delay(r["epilogue"])
        o.write(out[v % len(out)])
    return o.done()


def combine(fifos, r):
    """Per node: read its self-feature and each aggregate, then after the
    update's ``update_delay`` cycles write the node's output."""
    o = Ops()
    skip, out = fifos[r["skip"]], fifos[r["out"]]
    aggs = [fifos[name] for name in r["aggs"]]
    for v in range(r["count"]):
        o.read(skip[v % len(skip)])
        for a in aggs:
            o.read(a[v % len(a)])
        o.delay(r["update_delay"])
        o.write(out[v % len(out)])
    return o.done()
