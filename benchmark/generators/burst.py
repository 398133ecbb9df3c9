"""Open loop: every `period_s` a burst of `burst` operations falls due
at once, the first at the window's start, none in the last
`quiet_tail_s` seconds (the last burst drains inside the window). The
schedule is the same for every seed: the seed changes what is written,
not when."""


def plan(params: dict, seed: int, seconds: float) -> dict:
    period = float(params["period_s"])
    last = seconds - float(params.get("quiet_tail_s", 0.0))
    due = []
    k = 0
    while k * period < last or k == 0:
        due.extend([k * period] * int(params["burst"]))
        k += 1
    return {"kind": "open", "due": due}
