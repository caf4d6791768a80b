"""pack_occupancy: percent of the slots the scheduler launched that held a
job: jobs dispatched in the traced window over packs launched in it times
`max_pack`.  Read from the scheduler's own counters."""


def read(run):
    before, after = run.counters["before"], run.counters["after"]
    if "packs_launched" not in after:
        return None
    packs = after["packs_launched"] - before["packs_launched"]
    if packs <= 0:
        return None
    status = after["jobs"]
    dispatched = sum(1 for j in run.jobs
                     if status.get(j.handle, {}).get("status")
                     not in (None, "queued"))
    return 100.0 * dispatched / (packs * after["max_pack"])
