"""Convolution and matmul events against the compute peak: the least time
the step's convolution and matmul operations need at the chip's peak, over
the summed device time of the trace's events of those categories, per step.
Compute-bound by construction (the bound it is held to is operations over
the peak). params: `categories`, `name_has` (the group);
`kernel_categories`: where events of such a category run (an attention
kernel of its own), the attention products are in them and are left out of
the operations. On the TPU the group is the `kOutput` fusions, which carry
the elementwise work fused into each product as well: the share is of the
time of those fusions, and the trace cannot split them further."""
import trace as trace_mod


def read(view, params):
    if not view.devices or not view.traced or view.peaks is None:
        return None
    cell, plane = view.cell, trace_mod.fullest(view.devices)
    ns = trace_mod.group_ns(view.loaded, plane, params.get("categories", ()),
                            params.get("name_has", ()))
    if ns == 0:
        return None
    kernels = params.get("kernel_categories", ())
    own_kernel = bool(kernels) and trace_mod.group_ns(
        view.loaded, plane, kernels) > 0
    ops = view.flops.mxu_flops_per_item(cell.config, cell.traffic,
                                        exclude_attention=own_kernel) \
        * cell.items_per_step() / view.chips
    least_s = ops / view.peaks["flops_per_s"]
    return 100.0 * least_s / (ns / 1e9 / view.traced["steps"])
