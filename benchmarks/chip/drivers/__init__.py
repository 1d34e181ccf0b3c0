"""General traffic generators, each named by a traffic file's ``driver``."""
