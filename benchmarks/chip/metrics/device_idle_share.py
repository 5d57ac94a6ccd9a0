"""Percent of the traced window in which no op ran on the device: one
minus the union of the device's op intervals over the window, averaged
over the chips used."""


def read(ctx):
    share = ctx["trace"].idle_share(ctx["records"])
    return None if share is None else 100.0 * share
