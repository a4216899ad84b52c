"""Plain references of the architectures the benchmark's configurations
name (a configuration file's ``reference`` key picks the module)."""
