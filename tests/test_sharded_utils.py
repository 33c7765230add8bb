"""Host-side unit tests for the sharded engines' helpers (no device mesh
needed; the full sharded behavior runs under the virtual-mesh subprocess
suite in test_multichip_sharded.py)."""

from keto_tpu.parallel import ShardedServingEngine


class TestShardedBucket:
    def test_bucket_batch_terminates_for_non_power_of_two_data_axis(self):
        class Dummy:
            pass

        for n_data in (1, 2, 3, 5, 6, 7, 8):
            eng = Dummy()
            eng.n_data = n_data
            for n in (1, 7, 8, 9, 100, 4096):
                b = ShardedServingEngine._bucket_batch(eng, n)
                assert b >= n
                assert b % n_data == 0
                per = b // n_data
                assert per & (per - 1) == 0  # per-device slice is a pow2
