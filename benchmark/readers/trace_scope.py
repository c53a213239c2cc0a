"""Device time by model component: each trace event's self time goes to the
(component, phase) of its instruction in the program's own optimized module.

A trace event is named by its HLO line without metadata; the line begins with
the instruction's name. The program registers how to print the module it ran
(`raft_stereo_tpu.obs.scopes`), where every instruction carries the `op_name`
its scopes wrote, and ONE table there maps a path to a component. An event
whose name no registered module has (the small pad / unpad programs of the
same window), or whose opcode differs from the instruction's of that name,
counts as `unscoped`.

`components` / `phases`: what to sum (phases: all when left out). `per`: a
window key (`work`, `kernel_calls`, `attempted`) -> milliseconds per unit, or
`busy` -> per cent of the trace's busy seconds. A program without
`obs.scopes`, nothing registered, or no event placed -> None.
"""


def by_component(context):
    """{(component, phase): seconds} over the whole trace; None where there
    is nothing to join (the program's modules are printed once, by the
    registry, however often this is called)."""
    try:
        from raft_stereo_tpu.obs import scopes
    except ImportError:
        return None
    modules = list(scopes.registered().values())
    totals, placed = {}, 0.0
    for name, seconds in context["trace"]["device_time_by_name_s"].items():
        key = ("unscoped", "forward")
        event = scopes.parse_instruction(name)  # (name, opcode, "")
        for module in modules if event else ():
            op_name, opcode = module.get(event[0], (None, None))
            if opcode == event[1]:
                key = scopes.component(op_name, opcode)
                placed += seconds
                break
        totals[key] = totals.get(key, 0.0) + seconds
    return totals if placed > 0 else None


def read(context, components, per, phases=None):
    found = by_component(context)
    if found is None:
        return None
    seconds = sum(
        s for (component, phase), s in found.items()
        if component in components and (phases is None or phase in phases)
    )
    if per == "busy":
        busy = context["trace"]["busy_s"]
        return 100.0 * seconds / busy if busy > 0 else None
    units = context["window"].get(per, 0)
    return 1000.0 * seconds / units if units > 0 else None
