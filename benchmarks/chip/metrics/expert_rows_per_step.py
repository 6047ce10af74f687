"""Rows each held expert computes per decode step (expert layer; moves
``output_tok_s``): the routed assignments of the decode steps' rows to the
experts held on this chip, summed over the expert layers, over the held
experts times the expert layers times the decode steps of the traced window.
Read from the program's tick log (``repro.serving.telemetry``, its
``expert_rows``), stamped on the host clock of ``run.traced``.  Returns
nothing without that log or that count."""


def read(run):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    recs = telemetry.records(*run.traced)
    if not recs or not hasattr(recs[0], "expert_rows"):
        return None
    m = run.model
    e = m.get("moe")
    steps = run.rec.decode_steps(*run.traced)
    rows = sum(r.expert_rows for r in recs)
    if not e or not steps or not rows:
        return None
    pattern = m.get("block_pattern", ["attn"])
    k = m.get("first_k_dense", 0)
    expert_layers = sum(1 for i in range(k, m["n_layers"])
                        if pattern[(i - k) % len(pattern)] == "attn_moe")
    held = e.get("n_held") or e["n_experts"]
    return rows / (held * expert_layers * steps)
