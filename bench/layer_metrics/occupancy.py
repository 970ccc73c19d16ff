"""Share of the dispatched bucket slots that carried real query rows (%),
from ``PipelineStats`` over the window."""


def read(run):
    slots = run.stats1.batch_slots - run.stats0.batch_slots
    if not slots:
        return None
    return (run.stats1.batch_rows - run.stats0.batch_rows) / slots * 100.0
