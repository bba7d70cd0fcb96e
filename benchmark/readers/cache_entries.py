"""Entries this run added to the persistent compilation cache: 0 on every
run but a checkout's first."""


def read(ctx, params):
    return ctx["sut"].cache_entries_added()
