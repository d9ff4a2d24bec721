"""Prompt tokens a second while the slots are filled: the prompt tokens of
the requests sent before the window opened, over the seconds from the first
of their submits to the last of their first tokens (the host's clock).
With one client a slot these are the whole request set, prefilled back to
back with nothing else to do but the steps of the slots already filled: the
one timing of long prefills in a row that a cell has."""


def read(run):
    window = run["window"]
    filled = [r for r in window["requests"]
              if r.sent < window["t0"] and r.token_times]
    if not filled:
        return None
    took = max(r.token_times[0] for r in filled) \
        - min(r.sent for r in filled)
    return sum(len(r.prompt) for r in filled) / took if took > 0 else None
