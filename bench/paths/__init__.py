"""Set-up and measured loop of each entry point, found by the path named in a config file."""
