"""front door (serving/server.py): the median, over the window's requests,
of the client's time to first token (from sending) less the server's own
`timing.ttft_s` of the summary line: HTTP parsing, threads, the stream."""
import statistics


def read(facts):
    xs = [1e3 * ((r["t_tokens"][0] - r["t_sent"]) - r["timing"]["ttft_s"])
          for r in facts["load"]["records"]
          if r["t_tokens"] and r["t_sent"] and r.get("timing")
          and r["timing"].get("ttft_s") is not None]
    return statistics.median(xs) if xs else None
