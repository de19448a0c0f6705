"""Not a fault: a replica that says, the first time in a directory, that
it compiled its programs itself (as the first run of a checkout does)."""
import os

from kinds import serve as base


class ColdOnce(base.BenchReplica):
    def cache_writes_at_init(self, counted):
        mark = os.path.join(os.environ["PB_TEST_MARK_DIR"], "compiled")
        if os.path.exists(mark):
            return counted
        open(mark, "w").close()
        return 20


def run(ctx):
    return base.run(ctx, replica_cls=ColdOnce)
