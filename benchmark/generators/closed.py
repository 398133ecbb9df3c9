"""Closed loop: keep `outstanding` operations in flight for the whole
window, sending the next as one is confirmed. `max_rate` bounds how many
operations are signed in set-up (outstanding + max_rate * seconds)."""


def plan(params: dict, seed: int, seconds: float) -> dict:
    outstanding = int(params["outstanding"])
    return {"kind": "closed", "outstanding": outstanding,
            "max_ops": outstanding + int(params["max_rate"] * seconds)}
