"""``pad_fraction.*``: the share of launched rows that were padding,
``1 - live rows / launched rows`` over the window's launches, from the
batcher's own launch record (``DynamicImageBatcher.launches``), in %."""


def read(run):
    rows = sum(b for b, _ in run.launches)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(live for _, live in run.launches) / rows)
