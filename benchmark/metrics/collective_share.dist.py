"""Share of the chips' busy time spent in collectives, in %: the leaf-op
seconds of the traced window whose HLO name starts with `all-gather`,
`all-reduce`, `all-to-all`, `collective-permute` or `reduce-scatter`
(their async `-start`/`-done` halves included), summed over the chips,
over the chips' summed busy time. Op names are read, not scopes: an
executable from the persistent cache carries no scope metadata. The
seconds of each such op go to the notes as `collective_ops_s`."""

PREFIXES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
            "reduce-scatter")


def read(run):
    tr = run.trace
    if tr is None or tr.busy_s <= 0:
        return None
    ops = {k: v for k, v in tr.op_seconds.items()
           if k.split(" ", 1)[0].startswith(PREFIXES)}
    run.notes["collective_ops_s"] = dict(
        sorted(ops.items(), key=lambda kv: -kv[1]))
    return 100.0 * sum(ops.values()) / (tr.busy_s * tr.chips)
