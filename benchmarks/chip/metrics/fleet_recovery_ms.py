"""Fleet layer: kill of a replica to the first frame its replacement
served (``FleetStats.recovery_ms``, the fleet's own counter)."""


def read(rec):
    return rec["counters"].get("recovery_ms")
