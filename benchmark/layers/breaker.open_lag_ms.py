"""Mean time from the start of a dependency's outage to its breaker's trip
(CLOSED -> OPEN), in ms, from the program's breaker state-change record
(``engine.breaker_transitions()``), read once after the window by the
outage driver (``drivers/outage.py`` ``layer_data``). Reads nothing where
the driver or the program keeps no such record."""

NAME = "breaker.open_lag_ms"


def read(run: dict):
    return (run.get("layers") or {}).get(NAME)
