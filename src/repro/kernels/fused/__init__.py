"""Fused verify+decrypt: SHA-256 digests and AES-CTR plaintext from one
device program per tile, a lane-dense keystream pass and then one SHA
walk that XORs it in (see ``fusedp``). ``fused_verify_decrypt`` is the
registry-facing hook."""
from repro.kernels.fused.ops import fused_verify_decrypt

__all__ = ["fused_verify_decrypt"]
