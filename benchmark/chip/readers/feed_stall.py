"""Share of the window the consumer spent waiting on an empty feed queue
(`DeviceFeed.stall_seconds` over the window)."""


def read(view, params):
    w = view.window
    return 100.0 * w["feed_stall_s"] / w["seconds"]
