"""Buckets re-executed per request by the retry ladder or the exact
fallback: the program's ``reruns`` counter (traced run)."""
import program_spans


def read(run):
    recs = program_spans.records()
    if recs is None or not run.sent:
        return None
    return program_spans.counted(recs, "reruns") / len(run.sent)
