"""Mean number of slots decoded per decode step (the rows
``pool.step`` reports decoded, over the steps that decoded)."""


def read(ctx):
    c = ctx["counters"]
    return c["decode_rows"] / c["decode_steps"] if c.get("decode_steps") else None
