"""What the per-layer readers (``metrics/*.py``) read of the program's own
spans and counters (``repro/spans.py``).

The program keeps its records and counters while the profiler runs, which
a traced run starts just before the pre-roll and stops at the end of the
window: so they hold the pre-roll's 1 s as well as the window.  Every
reader returns None where the program keeps no spans (a program that
predates them) or no record of the name."""

import statistics


def store():
    """The program's span store, or None where it has none."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans


def median_ms(name):
    """Median duration of the records named ``name``, in milliseconds; the
    median makes the pre-roll's records negligible."""
    spans = store()
    recs = spans.records(name) if spans is not None else []
    if not recs:
        return None
    return 1e3 * statistics.median(r.seconds for r in recs)
