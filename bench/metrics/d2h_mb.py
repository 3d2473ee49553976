"""Megabytes per request copied from the device to the host: the
program's ``d2h_bytes`` counter (the executor's true row counts, retry
splices and the padded output copied in ``reassemble``; traced run)."""
import program_spans


def read(run):
    recs = program_spans.records()
    if recs is None or not run.sent:
        return None
    return program_spans.counted(recs, "d2h_bytes") / len(run.sent) / 1e6
