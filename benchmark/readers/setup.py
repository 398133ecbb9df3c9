"""Process start to the first due request, as run.py's drive() took it
on the harness's own clock just before the window opened."""


def read(spec, run):
    return run["setup_s"]
