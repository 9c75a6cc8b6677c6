"""Integration test of the collective-model bridge: the topology-aware
collective cost model (`core.collectives`), built from each topology's
saturation throughput under uniform traffic."""


def test_collective_model_orderings():
    """FHT beats Mesh for every collective kind and payload."""
    from repro.core.collectives import build_ici_model
    fht = build_ici_model("folded_hexa_torus", 64, "organic")
    mesh = build_ici_model("mesh", 64, "organic")
    for kind in ("all_reduce", "all_gather", "reduce_scatter",
                 "all_to_all"):
        for size in (2 ** 20, 2 ** 30):
            assert fht.collective_time_s(kind, size) < \
                mesh.collective_time_s(kind, size)
