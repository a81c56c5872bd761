"""Exposed time of one group of events (collectives) on the busiest device:
the time during which such an event runs and no other event does, over the
traced stretch. params: `categories`, `name_has`."""
import trace as trace_mod


def read(view, params):
    if not view.devices:
        return None
    plane = trace_mod.fullest(view.devices)
    cats, names = params.get("categories", ()), params.get("name_has", ())
    if trace_mod.group_ns(view.loaded, plane, cats, names) == 0:
        return None
    return 100.0 * trace_mod.exposed_ns(view.loaded, plane, cats, names) \
        / view.devices[plane]["window_ns"]
