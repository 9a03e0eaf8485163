"""A sharded-verifier class whose buckets do NOT round up to the mesh
width — handed to shardcheck.divisibility_violations by
tests/test_tmtrace.py to prove the gate turns red. Never imported by
production code."""


class BadSharded:
    """Mimics a sharded verifier's constructor contract but skips the
    round-up that makes every bucket divide by the mesh."""

    def __init__(self, mesh, bucket_sizes=None):
        self.mesh = mesh
        self.bucket_sizes = sorted(bucket_sizes or (8, 12, 100))

    def _bucket(self, n):
        for b in self.bucket_sizes:
            if b >= n:
                return b
        return n  # oversized: no mesh rounding either
