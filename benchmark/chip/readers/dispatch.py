"""Host time of one `trainer.step` call: the benchmark's own span around the
call, less the time the call spent blocked in the trainer's dispatch window
(the growth of `DispatchWindow.wait_seconds`), per step of the window."""


def read(view, params):
    w = view.window
    return 1e3 * (w["step_call_s"] - w["dispatch_wait_s"]) / w["steps"]
