"""whisper-base [audio] — 6L (enc+dec) d_model=512 8H d_ff=2048
vocab=51865 — enc-dec with the published front end: 80 log-mel bins,
3000 frames (30 s), two width-3 convolutions to 1500 positions.
Parameters float32, compute bfloat16. [arXiv:2212.04356]

Shapes map to the DECODER token axis (mechanical lowering; whisper's
designed decode context is 448 — positions wrap, noted in DESIGN.md).
long_500k SKIPPED: enc-dec with a bounded decoder context and full
attention; a 512k decode state is architecturally meaningless.
Attention params replicate (8 heads < model axis, 72M model).
"""
from repro.configs import base
from repro.models.encdec import EncDecConfig

ARCH_ID = "whisper-base"


def make_config() -> EncDecConfig:
    return EncDecConfig(
        name=ARCH_ID,
        enc_layers=6, dec_layers=6, d_model=512, n_heads=8, n_kv=8,
        head_dim=64, d_ff=2048, vocab=51865,
        max_source=1500, max_target=448,
        dtype="bfloat16", param_dtype="float32", n_mels=80,
    )


def make_smoke_config() -> EncDecConfig:
    return EncDecConfig(
        name=ARCH_ID + "-smoke",
        enc_layers=2, dec_layers=2, d_model=64, n_heads=4, n_kv=4,
        head_dim=16, d_ff=128, vocab=128, max_source=24, max_target=16,
        dtype="float32", param_dtype="float32", loss_chunk=8, n_mels=8,
    )


ARCH = base.ArchSpec(
    arch_id=ARCH_ID,
    citation="arXiv:2212.04356",
    kind="audio",
    make_config=make_config,
    make_smoke_config=make_smoke_config,
    engine="fedavg",
    param_rules=base.audio_param_rules(),
    cache_rules=base.audio_cache_rules(),
    long_policy="skip",
    skip_notes=("enc-dec with full attention and a 448-token decoder "
                "design context; long_500k decode state is meaningless "
                "for this architecture (DESIGN.md §Arch-applicability)."),
)
