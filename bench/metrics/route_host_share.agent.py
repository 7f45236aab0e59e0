"""Share of bench.route span time with no device op running, %."""
from bench import readers


def read(run):
    return readers.route_host_share(run)
