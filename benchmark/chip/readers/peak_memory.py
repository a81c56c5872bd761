"""Peak of device memory on the fullest chip after the window, in GB."""


def read(view, params):
    return view.memory_peak_bytes / 1e9 if view.memory_peak_bytes else None
