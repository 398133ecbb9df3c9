"""Arrival schedules, one module each, found by the `generator` name in
a traffic file. Each has ``plan(params, seed, seconds) -> dict``:

  {"kind": "open", "due": [seconds from the window's start, ascending]}
  {"kind": "closed", "outstanding": k, "max_ops": n}

The window loop in window.py drives either kind; a new mix is a new data
file, a new arrival law is one new module here."""
import importlib


def plan(traffic: dict, seed: int, seconds: float) -> dict:
    mod = importlib.import_module("generators." + traffic["generator"])
    return mod.plan(traffic["params"], seed, seconds)
