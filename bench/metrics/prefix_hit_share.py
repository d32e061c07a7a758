"""KV pool and prefix cache: prompt tokens adopted from the cache as a
share of prompt tokens the window admitted (adopted + prefilled)."""


def read(ctx):
    s = ctx["window"]["stats"]
    total = s["prefill_skipped_tokens"] + s["prefill_tokens"]
    return 100.0 * s["prefill_skipped_tokens"] / total if total else None
