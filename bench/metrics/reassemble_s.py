"""Mean host seconds per request inside ``reassemble``, from the benchmark's span
around that entry point (traced run)."""


def read(run):
    if run.spans is None or not run.sent:
        return None
    return run.spans.total("reassemble") / len(run.sent)
