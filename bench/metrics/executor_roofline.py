"""Share of the bandwidth roofline reached by the executors: the bytes the
window's products must move (``roofline.spgemm_bytes``) at the chip's
published HBM bandwidth, over the device time of the executor programs
(every executor the planner builds is a jit named ``run``) in the trace."""
import roofline

EXECUTOR = "jit_run"


def read(run):
    if run.trace is None:
        return None
    device_s = sum(v for k, v in run.trace["modules"].items()
                   if k == EXECUTOR)
    if device_s <= 0:
        return None
    work = sum(run.work_bytes[s.key] for s in run.answered)
    return 100.0 * work / roofline.peak(run.device_kind) / device_s
