"""Share of the bandwidth roofline reached by the executors: the bytes the
window's products must move (``roofline.spgemm_bytes``) at the published
HBM bandwidth of the cell's chips (``chips`` × one chip's), over the device
time of the executor programs in the trace.

Every executor the planner builds is a jit named ``run`` (module
``jit_run``) on one chip, and a jit of a ``shard_map`` over ``shard_fn``
(module ``jit_shard_fn``) on a mesh.  Module times come from
``tracing.summarize`` averaged over the device planes, so a product spread
over four chips is held against four chips' bandwidth for the time each of
them spent on it.  ``device_idle`` reads busy time averaged the same way,
a share of one chip's window, and takes no chip count."""
import roofline

EXECUTORS = ("jit_run", "jit_shard_fn")


def read(run):
    if run.trace is None:
        return None
    device_s = sum(v for k, v in run.trace["modules"].items()
                   if k in EXECUTORS)
    if device_s <= 0:
        return None
    work = sum(run.work_bytes[s.key] for s in run.answered)
    peak = run.chips * roofline.peak(run.device_kind)
    return 100.0 * work / peak / device_s
