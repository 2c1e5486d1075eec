"""One driver per served path, named by a configuration's "system"."""
