# Launch layer: production meshes, sharding rules, drivers, dry-run,
# roofline. Import modules directly (repro.launch.mesh etc.); this
# package intentionally avoids importing jax at package-import time so
# dryrun.main() can set XLA_FLAGS before any jax initialization.
