from repro.core import (  # noqa: F401
    aggregation,
    allocation,
    bounds,
    chain,
    detection,
    dp,
    lazy,
    mining,
    rounds,
    telemetry,
    topology,
)
