"""skylark_graph_se: approximate adjacency spectral embedding of a graph.

TPU-native analog of ref: ml/skylark_graph_se.cpp — reads an arc-list
graph, runs ApproximateASE, writes prefix.V.txt (embedding vectors) and
prefix.index.txt (vertex order).
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="skylark_graph_se",
        description="Approximate adjacency spectral embedding "
        "(ref: ml/skylark_graph_se.cpp)",
    )
    p.add_argument("graphfile", help="arc-list graph file")
    p.add_argument("-s", "--seed", type=int, default=38734)
    p.add_argument("-k", "--rank", type=int, default=6)
    p.add_argument("-i", "--powerits", type=int, default=2)
    p.add_argument("--skipqr", action="store_true")
    p.add_argument("-r", "--ratio", type=int, default=2)
    p.add_argument("-a", "--additive", type=int, default=0)
    p.add_argument("-n", "--numeric", action="store_true",
                   help="vertex names are numeric ids")
    p.add_argument("--prefix", default="out")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from libskylark_tpu.base.context import Context
    from libskylark_tpu.cli import write_ascii_matrix
    from libskylark_tpu.ml.graph import Graph, approximate_ase
    from libskylark_tpu.nla.svd import ApproximateSVDParams

    t0 = time.time()
    G = Graph()
    with open(args.graphfile) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            toks = line.split()
            u, v = toks[0], toks[1]
            if args.numeric:
                u, v = int(u), int(v)
            G.add_edge(u, v)
    print(f"Reading the graph... took {time.time() - t0:.2e} sec")

    params = ApproximateSVDParams(
        num_iterations=args.powerits,
        oversampling_ratio=args.ratio,
        oversampling_additive=args.additive,
        skip_qr=args.skipqr,
    )
    t0 = time.time()
    X, indexmap = approximate_ase(G, args.rank, Context(seed=args.seed),
                                  params)
    print(f"Computing embeddings... took {time.time() - t0:.2e} sec")

    write_ascii_matrix(args.prefix + ".V.txt", X)
    with open(args.prefix + ".index.txt", "w") as f:
        for v in indexmap:
            f.write(f"{v}\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
