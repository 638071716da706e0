"""Program side of each model family: a configuration file turned into the
program's own configuration, parameter tree and serving engine.  One
module per family, found by the ``family`` key of a configuration."""
