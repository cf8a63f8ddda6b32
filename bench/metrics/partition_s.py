"""Host seconds in ``core.partition.partition`` during set-up."""


def read(run):
    return run.stages["partition_s"]
