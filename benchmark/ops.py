"""Operations and bytes one Ed25519 verification needs, as a function of
the number of signatures and of nothing else: not the padded bucket, not
which kernel ran. A copy of PROFILE.md's inventory of the fused tail
(13-bit limbs, schoolbook field multiplication on the VPU):

  field multiplication  400 int32 multiplies + ~740 adds (tree sum)
                        + ~300 (reduce and three carry rounds)
  per signature         64 windows x ~44 multiplications (2816)
                        + decompress and encode (~800)  = ~3600
  10,000 signatures     1.47e10 multiplies + 3.8e10 adds, shifts and
                        selects = 5.3e10 scalar VPU operations

so 5.3e6 a signature. Bytes: the packed input row (message blocks,
signature, key) and one mask byte; the fused tail's ~10 MB for 10,000
is 1 KB a signature.
"""

SCALAR_OPS_PER_SIGNATURE = 5.3e6
BYTES_PER_SIGNATURE = 1024


def verify_ops(signatures: int) -> float:
    return SCALAR_OPS_PER_SIGNATURE * signatures


def verify_bytes(signatures: int) -> float:
    return BYTES_PER_SIGNATURE * signatures
