"""The whole step's share of the chip's peak: operations the forward and
backward passes need per item (flops/<family>.py, from the configuration's
shapes; recomputation not counted) times the items a second of the traced
stretch, over chips times the peak."""


def read(view, params):
    t = view.traced
    if not t or view.peaks is None:
        return None
    cell = view.cell
    rate = t["steps"] * cell.items_per_step() / t["seconds"]
    per_item = view.flops.train_flops_per_item(cell.config, cell.traffic)
    return 100.0 * per_item * rate / (view.chips * view.peaks["flops_per_s"])
