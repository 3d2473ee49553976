"""Output slots the executors allocated per nonzero of the answers: the
program's ``out_slots`` counter (the element count of every output buffer
an executor returned, reruns included) over NNZ(C) of the answered
requests (traced run).  1 is no padding."""
import program_spans


def read(run):
    recs = program_spans.records()
    nnz = sum(run.nnz_c[s.key] for s in run.answered)
    if recs is None or not nnz:
        return None
    return program_spans.counted(recs, "out_slots") / nnz
