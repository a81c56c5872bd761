"""Share of the busiest device's busy time in one group of its events.
params: `categories` and `name_has` name the group (an event belongs by its
HLO category or by a substring of its name); `complement: true` takes every
event outside the group instead. Nothing to read where the group is empty
and not complemented."""
import trace as trace_mod


def read(view, params):
    if not view.devices:
        return None
    plane = trace_mod.fullest(view.devices)
    ns = trace_mod.group_ns(view.loaded, plane,
                            params.get("categories", ()),
                            params.get("name_has", ()))
    total = view.devices[plane]["op_ns"]
    if params.get("complement"):
        ns = total - ns
    elif ns == 0:
        return None
    return 100.0 * ns / total
