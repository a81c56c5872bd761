"""A kernel against its roofline: the least time the chip could take for the
kernel's work in one step, which is the larger of operations over the peak
FLOP/s and bytes over the peak bytes/s, over the summed device time of the
kernel's events per step. params: `categories` or `name_has` (the kernel's
events), `work`
(the function of flops/<family>.py that gives (operations, bytes) a step).
Says on standard error which of the two bounds."""
import trace as trace_mod


def read(view, params):
    if not view.devices or not view.traced or view.peaks is None:
        return None
    cell, plane = view.cell, trace_mod.fullest(view.devices)
    ns = trace_mod.group_ns(view.loaded, plane, params.get("categories", ()),
                            params.get("name_has", ()))
    if ns == 0:
        return None
    ops, nbytes = getattr(view.flops, params["work"])(cell.config,
                                                      cell.traffic)
    t_ops = ops / view.chips / view.peaks["flops_per_s"]
    t_bytes = nbytes / view.chips / view.peaks["bytes_per_s"]
    view.say(f"kernel_roofline {params['work']}: bound by "
             f"{'operations' if t_ops >= t_bytes else 'bytes'} "
             f"({t_ops * 1e3:.3f} ms against {t_bytes * 1e3:.3f} ms a step)")
    return 100.0 * max(t_ops, t_bytes) / (ns / 1e9 / view.traced["steps"])
