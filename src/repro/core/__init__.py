"""repro.core — the paper's contribution: FoldedHexaTorus and the ICI
topology evaluation pipeline (topologies, routing, simulator, cost models,
the topology-aware collective cost model, and the mesh-to-chiplet flow
mapping of the collective workloads)."""
from .topology import Topology, build, GENERATORS, N_CONSTRAINTS, \
    make_topology, register_topology, unregister_topology, \
    validate_edges, valid_n, nearest_valid_n  # noqa
from .routing import Routing, build_routing, dependency_graph_is_acyclic, \
    routing_for, routing_cache_info, routing_cache_clear  # noqa
from .simulator import SimConfig, simulate, saturation_throughput, \
    zero_load_latency  # noqa
from . import traffic, costmodel, linkmodel, placement, collectives  # noqa
