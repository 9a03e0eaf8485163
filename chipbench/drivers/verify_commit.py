"""Entry `types.validation.verify_commit`: every signature of the
commit, as consensus and block validation check a LastCommit."""

from chipbench.commit_driver import CommitDriver


def setup(config: dict, traffic: dict, seed: int) -> CommitDriver:
    return CommitDriver(config, traffic, seed, light=False)
