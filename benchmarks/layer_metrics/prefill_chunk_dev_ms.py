"""Device milliseconds per execution of the serving prefill program (one
chunk): what ``prefill_chunk_ms`` reads, in a cell whose end-to-end metric a
chunk moves is the gap between tokens — a tick that carries a chunk is that
much longer for every row that decodes in it."""

from benchmarks import manifest as manifest_mod


def read(spans, facts, trace, info):
    same = manifest_mod.load_module(
        info["ctx"].manifest.reader_path("prefill_chunk_ms"))
    return same.read(spans, facts, trace, info)
