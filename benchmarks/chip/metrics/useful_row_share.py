"""Share of the rows the client update computed that hold data (%): the
program's counters ``client.rows_real`` over ``client.rows_computed``
(local steps x batch per client) in the traced window. None where the
program counted nothing."""


def read(w):
    counts = (getattr(w, "program", None) or {}).get("counters") or {}
    computed = counts.get("client.rows_computed", 0)
    if computed <= 0:
        return None
    return 100.0 * counts.get("client.rows_real", 0) / computed
