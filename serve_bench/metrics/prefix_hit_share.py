"""Share of admitted prompt tokens served from the prefix cache, in %:
window differences of the engine's ``prefix_hit_tokens`` over those plus
the prompt tokens prefilled (``prefill_tokens``)."""


def read(rec):
    s = rec["stats"]
    total = s.get("prefix_hit_tokens", 0) + s.get("prefill_tokens", 0)
    return 100.0 * s["prefix_hit_tokens"] / total if total else None
