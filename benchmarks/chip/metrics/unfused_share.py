"""unfused_share: the packed convs' MACs (the benchmark's own count,
chipbench.ops) of the layers the program lowered to the unfused
reference path, as a share of all packed convs' MACs.  Reads the
program's lowering records (``repro.obs.MetricsRegistry.lowerings``, one
per layer, kept whether or not the registry is enabled) in the process
default registry, where the engine's compiles wrote them during set-up.
Where the program keeps no such record, it reads nothing.  Notes how
many layers took each path."""

from chipbench import ops


def read(run):
    from repro import obs

    lowerings = getattr(obs.default_registry(), "lowerings", None)
    if lowerings is None:
        return None
    path = {r.layer: r.path for r in lowerings()}
    convs = [l for l in ops.layers(run.cell.conf)
             if l.packed and l.kind == "conv"]
    if not path or not convs:
        return None
    counts = {}
    for l in convs:
        p = path.get(l.name, "not recorded")
        counts[p] = counts.get(p, 0) + 1
    run.notes.append("lowering of the packed convs: " + ", ".join(
        f"{p} {n}" for p, n in sorted(counts.items())))
    unfused = sum(l.macs for l in convs if path.get(l.name) == "unfused")
    return 100.0 * unfused / sum(l.macs for l in convs)
