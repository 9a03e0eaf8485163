"""Host time a request spends decoding the commit from its wire bytes
(`Commit.from_proto`), which a node pays for every block it is sent:
the driver's own clock around the decode, over the window's requests."""


def read(ctx):
    took = ctx.driver.decode_s[-ctx.requests :] if ctx.requests else []
    if not took:
        return None
    return sum(took) * 1e3 / ctx.requests
