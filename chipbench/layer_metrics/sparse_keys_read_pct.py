"""Cached tokens the full layers' queries selected over the cached tokens they could
have read (``stats['sparse_keys_selected'] / stats['sparse_keys_live']``, summed over
slots or a chunk call's real queries, full layers and steps): what share of its cache a
query's attention reads. 100 while every context is under ``index_topk``."""
from chipbench.layer_metrics import _sparse_latent_moe as S

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    got = S.counters(facts)
    return None if got is None else 100.0 * got[1] / got[0]
