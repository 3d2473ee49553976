"""Mean seconds per request the planner waited for the sampled
prediction's device outputs: the program's ``wait`` spans inside ``plan``
(traced run)."""
import program_spans


def read(run):
    recs = program_spans.records()
    if recs is None or not run.sent:
        return None
    return program_spans.seconds(recs, "wait", under="plan") / len(run.sent)
