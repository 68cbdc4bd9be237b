"""client side: the 90th percentile, over all requests due in the window,
of first streamed token minus the time the request was due (a failed,
refused or unfinished request counts as the worst). The tail a user feels;
it swings by a tenth and more from run to run on one code (PERF.md section
2), so it stands here and carries no bound."""


def read(facts):
    return (facts.get("client") or {}).get("ttft_ms_p90")
