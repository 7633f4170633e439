"""The engine modules, one per engine a traffic file can name."""
