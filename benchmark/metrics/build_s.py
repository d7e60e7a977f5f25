"""Index build time: the host clock around ``build``, ended in
``block_until_ready``."""


def read(ctx):
    return ctx.build_s
