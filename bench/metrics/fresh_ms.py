"""Mean over the window's writes of the time from just before the
client's ``apply_delta`` call until the new device layout is ready (host
clock): how long a user's write leaves answers stale."""


def read(run):
    if not run.write_s:
        return None
    return 1e3 * sum(run.write_s) / len(run.write_s)
