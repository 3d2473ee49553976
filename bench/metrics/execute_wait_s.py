"""Mean seconds per request the host waited for the executor's outputs:
the program's ``execute.wait`` spans, around the first host read of them
(traced run)."""
import program_spans


def read(run):
    recs = program_spans.records()
    if recs is None or not run.sent:
        return None
    return program_spans.seconds(recs, "execute.wait") / len(run.sent)
