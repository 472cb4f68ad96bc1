"""How late the load generator ran: send instant less due instant, as the
``q``-th percentile over the requests due in the window, in milliseconds."""

from benchmark.harness import percentile


def read(obs, params):
    late = [1e3 * (r["t_send"] - r["t_due"]) for r in obs.get("records", ())
            if r["in_window"] and r.get("t_send") is not None]
    return percentile(late, params.get("q", 99)) if late else None
