"""Launch: mesh, sharding rules, the distributed train step, dry-run."""
