"""mind [arXiv:1904.08030]: embed_dim=64, n_interests=4, capsule_iters=3,
multi-interest dynamic-routing retrieval (Tmall).

Layout: four user-profile fields (age, gender, city, device: the tower
concatenates their embeddings to every interest capsule) and one item
field of 5e7 ids, Tmall's scale; histories of 50 behaviours.  The tower
as served (``models/recsys/mind.py``): B2I routing over the first
K'_u = max(1, min(4, floor(log2 |history|))) capsules, then two ReLU
layers (256, then 64 wide; assumed) over capsule ++ profile.  Serving
runs the tower on the device per batch and scans an item slab for the
max over live interests (``kernels/mind_corpus_score.py``).

``make_config`` is the published deployment; ``make_chip_share`` is one
chip's share of it on a four-chip host that row-shards the item arena
and the catalogue four ways: 1.25e7 item ids (the benchmark's
``mind-retrieval12m`` configuration).
"""
from repro.configs.registry import RECSYS_SHAPES, ArchSpec, register
from repro.core.fields import CONTEXT, ITEM, FieldSpec, FeatureLayout
from repro.models.recsys.mind import MINDConfig

ITEM_VOCAB = 50_000_000


def make_layout(item_vocab: int = ITEM_VOCAB):
    ctx = [
        FieldSpec("age", 10, CONTEXT),
        FieldSpec("gender", 3, CONTEXT),
        FieldSpec("city", 1_000, CONTEXT),
        FieldSpec("device", 100, CONTEXT),
    ]
    item = [FieldSpec("item_id", item_vocab, ITEM)]
    return FeatureLayout(tuple(ctx + item))


def make_config() -> MINDConfig:
    return MINDConfig(layout=make_layout(), embed_dim=64, n_interests=4,
                      capsule_iters=3, seq_len=50, hidden=256)


def make_chip_share() -> MINDConfig:
    """One chip's share of the published deployment on a four-chip host
    that row-shards the item vocabulary: every width as published, the
    item field cut to a quarter, 1.25e7 ids."""
    return MINDConfig(layout=make_layout(ITEM_VOCAB // 4), embed_dim=64,
                      n_interests=4, capsule_iters=3, seq_len=50, hidden=256)


def make_smoke() -> MINDConfig:
    fields = tuple(
        [FieldSpec(f"c{i}", 16, CONTEXT) for i in range(2)]
        + [FieldSpec("item", 256, ITEM)]
    )
    return MINDConfig(layout=FeatureLayout(fields), embed_dim=16,
                      n_interests=3, capsule_iters=3, seq_len=8, hidden=32,
                      n_neg=4)


ARCH = register(ArchSpec(
    name="mind", family="recsys",
    make_config=make_config, make_smoke=make_smoke,
    shapes=RECSYS_SHAPES,
))
