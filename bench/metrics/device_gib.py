"""Device memory per chip of the engine program the window ran, in GiB:
``memory_analysis()`` arguments + outputs + temporaries - aliases."""


def read(run):
    return run.setup.device_bytes / 2**30
