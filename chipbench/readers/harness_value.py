"""A scalar the harness took itself (``setup_s``, ``correct_check_s``, ...)."""


def read(ctx, key: str):
    return ctx.harness.get(key)
