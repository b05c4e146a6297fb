"""Load generator (the benchmark's own): 95th percentile of send time minus
due time.  A generator that runs late makes a slow server look fast."""


def read(report):
    return report["window"].get("late_ms_p95")
