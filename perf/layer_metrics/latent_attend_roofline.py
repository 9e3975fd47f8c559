"""The latent decode-attention ops against the least time the chip could
take: the larger of (live latent pages x page size x 576 live columns x
2 bytes, once a layer, / peak bytes a second) and (context tokens x 64
heads x (576 + 512) x 2 operations a layer / peak operations a second),
``perf/axk1_costs.py``, from ``engine.round``'s ``latent_pages_live``
(mean over the window's decode-only rounds; a page's every row counted
as context, which overstates the operations by at most a page a slot),
over the device time of the ops under ``layer/attn_latent/attend`` in
one run of the decode program. At 121 FLOP/B against a ridge of 240 the
bytes bound it. Nothing where the program records no such counter or
scope. Device trace."""

from perf import axk1_costs
from perf.span_ring import serve_window


def latent_pages_live(record):
    """Mean ``latent_pages_live`` of the window's decode-only
    ``engine.round``s, or None where the program records none."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    pages = [r.attrs["latent_pages_live"] for r in records
             if r.name == "engine.round" and r.t0 >= t_open
             and r.t1 <= t_close and r.attrs
             and not r.attrs.get("prefilled") and r.attrs.get("decoded")
             and "latent_pages_live" in r.attrs]
    return sum(pages) / len(pages) if pages else None


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    pages = latent_pages_live(record)
    model, peak = record.get("model") or {}, record.get("peak")
    if not decode or pages is None or not peak \
            or "latent_width" not in model:
        return None
    seconds = decode["seconds"].get("layer/attn_latent/attend", 0.0) \
        / decode["runs"]
    if not seconds:
        return None
    floor = max(
        axk1_costs.latent_bytes(model, model["page_size"], pages)
        / peak["hbm_bytes_per_s"],
        axk1_costs.latent_flops(model, pages * model["page_size"])
        / peak["bf16_flops_per_s"])
    return 100.0 * floor / seconds
