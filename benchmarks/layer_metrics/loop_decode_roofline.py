"""100 x the least time a looped model's decode step could take over
the time the decode program took a step on the device, in the traced
window.

The least time is the bytes a step has to read at the HBM peak: the
weights once a pass and the head, as the configuration sizes them
(``costs_looped.decode_weight_bytes``), and the K/V of every attended
token in every cache layer (``serving.loop_kv_read_bytes`` over
``paged_decode_steps``: the untraced window's mean).  Against it stands the device's busy
time in the decode program's executions, every op of them: an op with
a name path belongs to the program its path starts with
(``jit(forward_decode_paged)/...``), and an op without one (the
compiler's ``copy-done`` / ``slice-done`` prefetches of the next
matrices, or a copy named after an argument, ``block_tables``) to the
program of the named op before it on its device's timeline, which is
the program running around it.
A sum by scope would leave those out and read over 100.  The steps the
traced window holds are its ``paged_attn`` calls over the cache
layers: the decode program calls the kernel once a layer and pass, and
no other program calls it."""

from benchmarks import costs_looped, reduce_trace


def program_seconds(red, program: str) -> float:
    """The device's busy time in ``program``'s ops, mean over the
    devices of the traced window: the union of their intervals, since
    the events of asynchronous copies overlap others without nesting in
    them (a sum of self times read 2% over the busy time of a trace
    that held the decode program alone, on the v5e)."""
    per_device = []
    for events in red.trace.devices.values():
        owner, unowned, mine = None, [], []
        for e in sorted(events, key=lambda e: (e.start, -e.end)):
            if e.path.startswith("jit("):
                owner = e.path.split("/", 1)[0]
                if owner == program:
                    # the ops before the window's first named one
                    mine += unowned
                unowned = []
            elif owner is None:
                unowned.append((e.start, e.end))
                continue
            if owner == program:
                mine.append((e.start, e.end))
        per_device.append(reduce_trace.total(reduce_trace.union(mine)))
    return sum(per_device) / len(per_device) if per_device else 0.0


def read(obs, params):
    red = obs.get("reduction")
    numbers = obs["numbers"]
    kv = numbers.get("counters.serving.loop_kv_read_bytes")
    steps = numbers.get("counters.serving.paged_decode_steps")
    model = obs["model"]
    if red is None or not kv or not steps or costs_looped.passes(model) < 2:
        return None
    calls = red.count(reduce_trace.matcher(params["patterns"], "any"))
    took = program_seconds(red, params["program"])
    if not calls or not took:
        return None
    least = (costs_looped.decode_weight_bytes(model) + kv / steps) \
        / obs["peaks"]["hbm_bytes_per_s"]
    obs.setdefault("notes", {})["loop_decode_roofline"] = "memory-bound"
    return 100.0 * calls / costs_looped.cache_layers(model) * least / took
