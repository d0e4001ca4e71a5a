"""95th percentile of time to first token (s): from each request's
scheduled send to its first streamed token, over the requests due in the
window; one with no first token by the close counts with its age then.
Host clock, the harness's own stamps.  About 40 requests fall in a
chat-short window, so the percentile rests on its two largest: a
per-layer reading, held to no bound."""

from bench.harness import end_to_end


def read(r):
    return end_to_end(r)["ttft_p95_s"][0]
