"""A reader the tests add from their own directory: how many times the
window went through the shard."""


def read(ctx):
    return ctx["counters"]["steps"] / ctx["traffic"]["batches"]
