"""Host seconds in ``BSPEngine.__init__`` (binding, hybrid plan,
device_put of the resident arrays) during set-up."""


def read(run):
    return run.stages["engine_build_s"]
