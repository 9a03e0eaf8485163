"""Entry `types.validation.verify_commit_light`: what blocksync calls
for every block it catches up on (blocksync/reactor.py)."""

from chipbench.commit_driver import CommitDriver


def setup(config: dict, traffic: dict, seed: int) -> CommitDriver:
    return CommitDriver(config, traffic, seed, light=True)
