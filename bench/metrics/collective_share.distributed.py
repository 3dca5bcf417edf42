"""collective_share.distributed: the share of each chip's busy time spent
in collective operations (the distributed step's all_to_all and the
all-reduce of its psum), averaged over the chips.  The profiler names each
operation by its HLO instruction text (`%all_to_all.7 = f32[4,1,100000]
all-to-all(%all_to_all.6), ...`, `%psum.7 = f32[] all-reduce(%fusion.8),
...`), so a collective is told by its opcode, asynchronous halves
included.  Collective time is the union of those operations; busy time the
union of every operation."""
import re

from bench.tracereduce import union_ns

_COLLECTIVE = re.compile(r"\s(all-to-all|all-reduce)(-start|-done)?\(")


def is_collective(name: str) -> bool:
    return _COLLECTIVE.search(name) is not None


def read(ctx):
    dev = ctx.device
    if dev is None or ctx.mix["kind"] != "distributed" or not dev.devices:
        return None
    shares = []
    for d in dev.devices.values():
        busy = union_ns([(a, b) for _, a, b in d["ops"]], dev.lo, dev.hi)
        if busy:
            collective = union_ns([(a, b) for name, a, b in d["ops"] if is_collective(name)], dev.lo, dev.hi)
            shares.append(collective / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
