"""Percent of host walks that ran to their end, not cut by their time
budget: walks whose journey `done` event says `cut` false, over walks
with a `done` event. The program sets `cut` where LASER returns for its
budget; a program that records no `cut` gives nothing to read."""


def read(run):
    cuts = []
    for events in run.get("journeys") or []:
        done = next(
            (r for r in events
             if r.get("tier") == "host-walk" and r.get("event") == "done"),
            None,
        )
        if done is not None:
            cuts.append((done.get("attrs") or {}).get("cut"))
    if not cuts or None in cuts:
        return None
    return 100.0 * sum(1 for c in cuts if not c) / len(cuts)
