"""rows_per_launch.serve: query rows per fused launch of the batching
service over the window (``rows_launched / n_launches``)."""


def read(ctx):
    st = ctx["stats"]
    n = st.get("launches", 0)
    return st["rows_launched"] / n if n else None
