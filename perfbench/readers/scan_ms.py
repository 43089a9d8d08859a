from . import mean_of


def _scan_seconds(m):
    return sum(op["metrics"].get("scanTime", 0.0) for op in m["operators"]
               if "Scan" in op["operator"])


def read(ctx):
    return mean_of(ctx, lambda m: _scan_seconds(m) * 1e3)
