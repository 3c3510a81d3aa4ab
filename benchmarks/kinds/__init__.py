"""One driver per traffic `kind`; `run.py` finds it by the name in the
traffic file. Each module has one entry, `run(run)`."""
