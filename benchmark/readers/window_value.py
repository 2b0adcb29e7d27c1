"""One value the runner took of the window itself, such as ``setup_s``
(process start to the reading that opens the window)."""


def read(ctx, key):
    return ctx["window"].get(key)
