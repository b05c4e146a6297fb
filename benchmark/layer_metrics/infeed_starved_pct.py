"""Infeed: share of the window the step loop spent between two dispatches
waiting for a batch (``goodput_infeed_starved_us``)."""


def read(report):
    d = report["window"]["delta"].get("trainer")
    if d is None:
        return None
    return (100.0 * d.get("goodput_infeed_starved_us", 0) / 1e6
            / report["window"]["seconds"])
