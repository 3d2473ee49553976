"""Mean host seconds per request inside ``execute``, from the benchmark's span
around that entry point (traced run)."""


def read(run):
    if run.spans is None or not run.sent:
        return None
    return run.spans.total("execute") / len(run.sent)
